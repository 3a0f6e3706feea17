"""A latent-attention, sparse-expert decoder (the ``deepseek_v3`` block)
for the serving path.

The second model family beside :mod:`apex_tpu.models.gpt`, named for
its two mechanisms:

- **MLA** — multi-head latent attention: queries through a low-rank
  bottleneck (``q_lora_rank``), keys and values through another
  (``kv_lora_rank``) whose normed output, with ONE rotary key shared by
  all heads, is all that is cached: ``kv_lora_rank + qk_rope_head_dim``
  values a token a layer, whatever the head count.  Rotary embedding is
  YaRN's blend of interpolated and extrapolated frequencies on a
  decoupled ``qk_rope_head_dim`` slice of each head;
- **sparse experts** — after ``num_dense_layers`` leading layers with a
  dense gated-SiLU FFN, every layer's FFN is a shared expert plus
  routed experts chosen by sigmoid scores, a choice-only bias and
  group-limited top-k (:func:`apex_tpu.transformer.expert_parallel
  .held_experts_ffn`), of which THIS process holds the static range
  ``held`` and computes that share only.

RMSNorm everywhere, no projection has a bias, the head is untied.

**One definition of each piece.**  :func:`forward` (a whole sequence:
the prefill, and the full forward the tests compare) and
:func:`forward_decode` (one token a slot over the paged latent cache)
are the same :func:`_block` — norms, :func:`mla_project`, the router
and expert FFN, the residual wiring — around two attention cores:
:func:`_attend_full` expands keys and values per head and runs the
flash forward kernel (q, k and v all ``qk_nope + qk_rope`` = ``v`` wide
here), the core inside :func:`forward_decode` writes the token's latent
column into the pool in place and runs the absorbed decode kernel
(:mod:`apex_tpu.ops.mla_decode_pallas`).  The dense and the expert
stack are two ``lax.scan``s in turn; the cache is the carry of both
and the layer index rides to the kernels as a scalar, as in
``gpt.forward_decode``.

Parameters are born in ``param_dtype`` (bf16 for serving: nothing is
cast per step; from another dtype, ``MLAMoEServed.serving_params``
casts the matrices once); matrices are stored ``(in, out)``.  Tensor-parallel
and training variants do not exist yet (ROADMAP, Queue 2); the
multi-token-prediction module of the published checkpoints is not
part of the model's logits and is not built.
"""

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from apex_tpu.transformer.expert_parallel import held_experts_ffn

__all__ = [
    "MLAMoEConfig", "MLAMoEServed", "COUNTER_NAMES", "forward",
    "forward_decode", "init_params", "mla_project", "yarn_inv_freq",
]

#: the device-side counters the decode step accumulates, in the order
#: of the carried vector
COUNTER_NAMES = ("moe_assignments_held", "moe_assignments_all",
                 "moe_experts_hit")


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    """Shapes and constants, under the published config's names where
    it has one.  ``n_routed_experts`` is the ROUTER's width;
    ``held_start``/``held_count`` say which of those experts this
    process holds (``held_count=None``: all of them)."""

    vocab_size: int = 128256
    hidden_size: int = 7168
    num_dense_layers: int = 3
    num_moe_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    held_start: int = 0
    held_count: Optional[int] = None
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 262144
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if self.held.stop > self.n_routed_experts or len(self.held) < 1:
            raise ValueError(f"held {self.held} outside the router's "
                             f"{self.n_routed_experts} experts")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "MLAMoEConfig":
        """From a ``deepseek_v3``-style ``config.json`` dict (the keys a
        configuration file keeps verbatim).  ``n_routed_experts`` there
        may be an int (all held) or be overridden together with
        ``held_start``/``held_count``."""
        rs = conf.get("rope_scaling") or {}
        dense = int(conf["first_k_dense_replace"])
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            num_dense_layers=dense,
            num_moe_layers=int(conf["num_hidden_layers"]) - dense,
            num_attention_heads=conf["num_attention_heads"],
            q_lora_rank=conf["q_lora_rank"],
            kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"],
            intermediate_size=conf["intermediate_size"],
            moe_intermediate_size=conf["moe_intermediate_size"],
            n_routed_experts=conf["n_routed_experts"],
            n_shared_experts=conf["n_shared_experts"],
            num_experts_per_tok=conf["num_experts_per_tok"],
            n_group=conf["n_group"], topk_group=conf["topk_group"],
            routed_scaling_factor=conf["routed_scaling_factor"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=float(conf["rope_theta"]),
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max_position=int(rs.get(
                "original_max_position_embeddings",
                conf["max_position_embeddings"])),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            max_position_embeddings=conf["max_position_embeddings"])
        kw.update(overrides)
        return cls(**kw)

    @property
    def num_layers(self) -> int:
        return self.num_dense_layers + self.num_moe_layers

    @property
    def held(self) -> range:
        n = (self.n_routed_experts - self.held_start
             if self.held_count is None else self.held_count)
        return range(self.held_start, self.held_start + n)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) \
                + 1.0
            scale *= m * m
        return scale

    def served_model(self) -> "MLAMoEServed":
        return MLAMoEServed(self)


# ------------------------------------------------------------- parameters
def _attn_shapes(c: MLAMoEConfig):
    H, heads = c.hidden_size, c.num_attention_heads
    return {
        "attn_norm": (H,), "wq_a": (H, c.q_lora_rank),
        "q_norm": (c.q_lora_rank,),
        "wq_b": (c.q_lora_rank, heads, c.qk_head_dim),
        "wkv_a": (H, c.latent_width), "kv_norm": (c.kv_lora_rank,),
        "wkv_b_k": (c.kv_lora_rank, heads, c.qk_nope_head_dim),
        "wkv_b_v": (c.kv_lora_rank, heads, c.v_head_dim),
        "wo": (heads * c.v_head_dim, H), "ffn_norm": (H,),
    }


def param_shapes(c: MLAMoEConfig) -> Dict:
    """The parameter tree as shapes: ``embed``/``head`` (V, H),
    ``final_norm``, and the two stacks ``dense`` and ``moe`` with a
    leading layer axis.  Norm gains end in ``norm``; ``router_bias`` is
    float32 (the router runs in float32), all else ``param_dtype``."""
    H, F, Fe = c.hidden_size, c.intermediate_size, c.moe_intermediate_size
    n_held, Fs = len(c.held), c.moe_intermediate_size * c.n_shared_experts
    dense = dict(_attn_shapes(c), w_gate=(H, F), w_up=(H, F), w_down=(F, H))
    moe = dict(_attn_shapes(c), router=(H, c.n_routed_experts),
               router_bias=(c.n_routed_experts,),
               we_gate=(n_held, H, Fe), we_up=(n_held, H, Fe),
               we_down=(n_held, Fe, H),
               ws_gate=(H, Fs), ws_up=(H, Fs), ws_down=(Fs, H))
    return {
        "embed": (c.vocab_size, H), "head": (c.vocab_size, H),
        "final_norm": (H,),
        "dense": {k: (c.num_dense_layers,) + v for k, v in dense.items()},
        "moe": {k: (c.num_moe_layers,) + v for k, v in moe.items()},
    }


def init_params(config: MLAMoEConfig, key, std: float = 0.02) -> Dict:
    """Seeded parameters in ``param_dtype``: matrices N(0, std), gains
    1 + N(0, std), the router's bias N(0, std / 2) in float32."""
    shapes = param_shapes(config)
    flat, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * std
        if name.endswith("norm"):
            x = 1.0 + x
        if name == "router_bias":
            out.append(0.5 * x)
        else:
            out.append(x.astype(config.param_dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ pieces
def _rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(c: MLAMoEConfig):
    """YaRN's rotary frequencies, (qk_rope_head_dim / 2,) float32: the
    published blend — dimensions that turn more than ``beta_fast``
    times over the original context keep their frequency, those that
    turn less than ``beta_slow`` times are interpolated by ``factor``,
    a linear ramp between."""
    d = c.qk_rope_head_dim
    extra = 1.0 / (c.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                    / d))
    if c.rope_factor <= 1.0:
        return extra

    def correction_dim(rotations):
        return d * math.log(c.rope_original_max_position
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(c.rope_theta))

    low = max(math.floor(correction_dim(c.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(c.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / c.rope_factor * (1.0 - keep) + extra * keep


def _rope(x, positions, c: MLAMoEConfig):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by
    ``positions * inv_freq[i]``; the result holds the rotated first
    elements, then the rotated second elements (the published code's
    de-interleaved order, the same for queries and keys).  ``x``:
    (T, ..., d); ``positions``: (T,)."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(c)[None]
    m = 1.0
    if c.rope_factor > 1.0 and c.rope_mscale_all_dim:
        m = (0.1 * c.rope_mscale * math.log(c.rope_factor) + 1.0) \
            / (0.1 * c.rope_mscale_all_dim * math.log(c.rope_factor) + 1.0)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), \
        (jnp.sin(ang) * m).reshape(shape)
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def mla_project(x, p, c: MLAMoEConfig, positions):
    """The MLA projections of normed activations ``x`` (T, H) at
    ``positions`` (T,): ``q_nope`` (T, heads, qk_nope), ``q_rope`` (T,
    heads, qk_rope) with its rotation applied, and ``latent`` (T,
    kv_lora_rank + qk_rope) — the normed compressed latent followed by
    the rotated shared key: exactly the column that is cached."""
    cd = c.compute_dtype
    c_q = _rms_norm(jnp.matmul(x, p["wq_a"].astype(cd)), p["q_norm"],
                    c.rms_norm_eps)
    q = jnp.einsum("tr,rhd->thd", c_q, p["wq_b"].astype(cd))
    q_nope, q_rope = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    kv = jnp.matmul(x, p["wkv_a"].astype(cd))
    c_kv = _rms_norm(kv[:, :c.kv_lora_rank], p["kv_norm"], c.rms_norm_eps)
    k_r = _rope(kv[:, c.kv_lora_rank:], positions, c)
    return q_nope, _rope(q_rope, positions, c), \
        jnp.concatenate([c_kv, k_r], axis=-1)


def _gated_ffn(x, w_gate, w_up, w_down):
    cd = x.dtype
    return jnp.matmul(jax.nn.silu(jnp.matmul(x, w_gate.astype(cd)))
                      * jnp.matmul(x, w_up.astype(cd)), w_down.astype(cd))


#: the held experts' weights: a layer loop leaves them STACKED and
#: passes the layer's index (``held_experts_ffn``'s ``layer``)
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def _expert_ffn(x, p, c: MLAMoEConfig, token_mask, impl="auto"):
    """The expert layer's FFN: the held experts' routed share plus the
    shared expert.  ``p["expert_layer"]``, where present, says that the
    expert leaves are stacked and which layer of them this is.
    Returns ``(out, counts)``."""
    routed, counts = held_experts_ffn(
        x, p, c.held, top_k=c.num_experts_per_tok, n_group=c.n_group,
        topk_group=c.topk_group, scale=c.routed_scaling_factor,
        token_mask=token_mask, layer=p.get("expert_layer"),
        impl={"auto": "auto", "pallas": "pallas"}.get(impl, "xla"))
    return routed + _gated_ffn(x, p["ws_gate"], p["ws_up"],
                               p["ws_down"]), counts


def _block(x, p, c: MLAMoEConfig, positions, attend, token_mask,
           impl="auto"):
    """One layer on (T, H) activations; ``attend(q_nope, q_rope, latent,
    p)`` is the attention core and returns the heads' outputs (T,
    heads, v_head_dim) with what it cached (the latent columns, or the
    pool it wrote them into).  A dense layer's ``p`` has ``w_gate``, an
    expert layer's ``router``.  Returns ``(x, counts or None,
    cached)``."""
    cd = c.compute_dtype
    q_nope, q_rope, latent = mla_project(
        _rms_norm(x, p["attn_norm"], c.rms_norm_eps), p, c, positions)
    o, cached = attend(q_nope, q_rope, latent, p)
    x = x + jnp.matmul(o.reshape(o.shape[0], -1).astype(cd),
                       p["wo"].astype(cd))
    h = _rms_norm(x, p["ffn_norm"], c.rms_norm_eps)
    if "router" in p:
        y, counts = _expert_ffn(h, p, c, token_mask, impl)
        return x + y, counts, cached
    return x + _gated_ffn(h, p["w_gate"], p["w_up"], p["w_down"]), None, \
        cached


def _attend_full(c: MLAMoEConfig, batch: int, attn_impl: str):
    """The whole-sequence attention core: keys and values expanded per
    head from the latent, causal flash attention."""
    from apex_tpu.ops.attention import flash_attention

    flash = {"auto": "auto", "pallas": "pallas"}.get(attn_impl, "scan")

    def attend(q_nope, q_rope, latent, p):
        cd = c.compute_dtype
        T, heads = q_nope.shape[0], c.num_attention_heads
        c_kv, k_r = latent[:, :c.kv_lora_rank], latent[:, c.kv_lora_rank:]
        k_nope = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_k"].astype(cd))
        v = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_v"].astype(cd))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, None], (T, heads,
                                                     k_r.shape[-1]))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        if q.shape[-1] != v.shape[-1]:
            raise NotImplementedError(
                "the flash kernel has one head size: qk_nope + qk_rope "
                "must equal v_head_dim")

        def bhsd(t):                      # (B*S, heads, d) -> (B, heads, S, d)
            return t.reshape(batch, T // batch, heads, -1) \
                .transpose(0, 2, 1, 3)

        o = flash_attention(bhsd(q), bhsd(k), bhsd(v), causal=True,
                            softmax_scale=c.softmax_scale, impl=flash)
        return o.transpose(0, 2, 1, 3).reshape(T, heads, -1), latent

    return attend


def _embed(params, tokens, c: MLAMoEConfig):
    return jnp.take(params["embed"], tokens, axis=0).astype(c.compute_dtype)


def _scan_stacks(params, c: MLAMoEConfig, body, carry):
    """Run ``body(carry, (layer params, layer index))`` over the dense
    stack, then the expert stack.  The held experts' weights are NOT
    scanned over: the body sees them whole, with ``expert_layer`` the
    layer's index among them (a scanned slice of a kernel's operand
    would be copied out first; ``held_experts_ffn``)."""
    ys = []
    for name, lo, n in (("dense", 0, c.num_dense_layers),
                        ("moe", c.num_dense_layers, c.num_moe_layers)):
        if not n:
            continue
        whole = {k: v for k, v in params[name].items()
                 if k in EXPERT_LEAVES}
        sliced = {k: v for k, v in params[name].items() if k not in whole}
        if whole:
            sliced["expert_layer"] = jnp.arange(n, dtype=jnp.int32)

        def step(carry, inp, whole=whole):
            p, li = inp
            return body(carry, (dict(p, **whole), li))

        carry, y = jax.lax.scan(
            step, carry, (sliced, lo + jnp.arange(n, dtype=jnp.int32)))
        ys.append(y)
    return carry, ys


def forward(params, tokens, config: MLAMoEConfig, attn_impl: str = "auto",
            return_hidden: bool = False, return_latent: bool = False,
            token_mask=None):
    """Full forward of (B, S) ``tokens``.  Returns float32 logits (B, S,
    V), or with ``return_hidden`` the final-normed activations (B, S,
    H); with ``return_latent`` also the per-layer cached columns (L, B,
    S, latent_width).  ``token_mask`` (B, S): padding routes to no
    expert."""
    c = config
    B, S = tokens.shape
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    attend = _attend_full(c, B, attn_impl)
    mask = None if token_mask is None else token_mask.reshape(B * S)

    def body(x, inp):
        x, _, latent = _block(x, inp[0], c, positions, attend, mask,
                              attn_impl)
        return x, latent

    x, ys = _scan_stacks(params, c, body,
                         _embed(params, tokens.reshape(B * S), c))
    x = _rms_norm(x, params["final_norm"], c.rms_norm_eps)
    out = x.reshape(B, S, -1)
    if not return_hidden:
        out = jnp.matmul(out.astype(jnp.float32),
                         params["head"].T.astype(jnp.float32))
    if return_latent:
        return out, jnp.concatenate(ys, axis=0).reshape(
            c.num_layers, B, S, c.latent_width)
    return out


def forward_decode(params, tokens, positions, active, pools, page_tables,
                   config: MLAMoEConfig, attn_impl: str = "auto",
                   verify_width: int = 1, write_mask=None):
    """One token a slot over the paged latent cache (the contract of
    :func:`apex_tpu.models.gpt.forward_decode`).

    ``pools``: the carried cache state — ``"latent"``, the (L,
    num_pages, 1, latent_width, page_size) pool, and optionally
    ``"counters"``, an int32 vector in :data:`COUNTER_NAMES`' order
    that this step adds to.  Each layer writes its tokens' latent
    columns in place (``apex_kv_write``) and attends in absorbed form
    (``apex_mla_decode_attention``): the query takes the key
    up-projection, the output the value up-projection.  Returns
    ``(hidden (B, H), pools)``, hidden final-normed.
    """
    from apex_tpu.inference.kv_cache import COUNTERS, write_decode_pools
    from apex_tpu.ops.mla_decode_pallas import mla_decode_attention

    c = config
    if verify_width != 1:
        raise NotImplementedError(
            "the latent decode kernel scores one position a slot: "
            "speculative verify and chunked prefill over a latent cache "
            "are not built (ROADMAP, Queue 2)")
    cd = c.compute_dtype
    positions = positions.astype(jnp.int32)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    if write_mask is None:
        write_mask = active

    def body(carry, inp):
        x, pool, counters = carry
        p, li = inp

        def attend(q_nope, q_rope, latent, p_):
            (new_pool,) = write_decode_pools(
                (pool,), (latent[:, None],), page_tables, positions,
                write_mask, layer=li, impl=attn_impl)
            q_lat = jnp.einsum("thd,chd->thc", q_nope,
                               p_["wkv_b_k"].astype(cd))
            o_lat = mla_decode_attention(
                jnp.concatenate([q_lat, q_rope], -1), new_pool, page_tables,
                lengths, c.kv_lora_rank, c.softmax_scale, impl=attn_impl,
                layer=li)
            return jnp.einsum("thc,chd->thd", o_lat.astype(cd),
                              p_["wkv_b_v"].astype(cd)), new_pool

        x, counts, pool = _block(x, p, c, positions, attend, active,
                                 attn_impl)
        if counts is not None and counters is not None:
            counters = counters + jnp.stack(
                [counts["assignments_held"], counts["assignments_all"],
                 counts["experts_hit"]]).astype(counters.dtype)
        return (x, pool, counters), None

    (x, pool, counters), _ = _scan_stacks(
        params, c, body,
        (_embed(params, tokens, c), pools["latent"], pools.get(COUNTERS)))
    out = dict(pools, latent=pool)
    if counters is not None:
        out[COUNTERS] = counters
    return _rms_norm(x, params["final_norm"], c.rms_norm_eps), out


# ----------------------------------------------------------- served model
class MLAMoEServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md)."""

    #: one position a slot a step: no speculative verify, no chunks
    multi_position = False
    counter_names = COUNTER_NAMES
    #: rotary positions: no learned table bounds a request
    max_positions = None
    #: the leaves of both stacks that :func:`forward` and
    #: :func:`forward_decode` read ONLY as ``leaf.astype(compute_dtype)``
    #: (``mla_project``, the two attention cores, ``_block``,
    #: ``_gated_ffn``): the attention projections, the dense FFN and the
    #: shared expert.  Not the router and its bias (float32), not a norm
    #: gain (float32), not the held experts' ``we_*`` (the grouped
    #: matmul reads them as they are stored), not ``embed``/``head``
    cast_once_leaves = ("wq_a", "wq_b", "wkv_a", "wkv_b_k", "wkv_b_v", "wo",
                        "w_gate", "w_up", "w_down",
                        "ws_gate", "ws_up", "ws_down")

    def __init__(self, config: MLAMoEConfig):
        self.config = config

    def cache_spec(self) -> Dict[str, tuple]:
        c = self.config
        return {"latent": (c.num_layers, 1, c.latent_width)}

    def head(self, params):
        return params["head"]

    def serving_params(self, params):
        """The tree to give the served programs: :attr:`cast_once_leaves`
        in ``compute_dtype``, every other leaf the array it was
        (:func:`apex_tpu.inference.decode.cast_once`).  Parameters born
        in the compute dtype (bf16 serving) pass through."""
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt -> final-normed hidden (S, 1, H) and the
        cached columns by pool name, (L, S, heads, dim) each."""
        S = prompt.shape[1]
        hidden, latent = forward(
            params, prompt, self.config, attn_impl=attn_impl,
            return_hidden=True, return_latent=True,
            token_mask=jnp.arange(S, dtype=jnp.int32)[None] < prompt_len)
        return hidden.transpose(1, 0, 2), {"latent": latent.transpose(
            0, 2, 1, 3)}

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)
