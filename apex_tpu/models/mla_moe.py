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

- **a mixer KIND per layer** (``layer_kinds``; ``linear_attn_config``
  of a ``kimi_linear`` config) — a layer's mixer is MLA or **KDA**,
  Kimi Delta Attention: a gated delta rule whose state, one ``dk x dv``
  float32 matrix a head, is all a sequence carries, beside the last
  ``conv - 1`` inputs of a short causal convolution
  (:mod:`apex_tpu.ops.kda`; :func:`_kda_inputs` has the equations).
  Such a model's MLA layers may have one query matrix (``q_lora_rank:
  null``) and no rotation at all (``mla_use_nope``).

RMSNorm everywhere, no projection has a bias, the head is untied.

**One definition of each piece.**  :func:`forward` (a whole sequence:
the prefill, and the full forward the tests compare) and
:func:`forward_decode` (one token a slot over the cache) are the same
:func:`_block` — norms, the router and expert FFN, the residual wiring
— around the layer's mixer, which comes in a whole-sequence and a
one-token form for each kind: :func:`_attend_full` expands keys and
values per head and runs the flash forward kernel, the MLA core inside
:func:`forward_decode` writes the token's latent column into the pool
in place and runs the absorbed decode kernel
(:mod:`apex_tpu.ops.mla_decode_pallas`); :func:`_kda_full` runs the
chunked delta rule and hands back the state and the convolution's tail
at the prompt's end, the KDA core inside :func:`forward_decode` updates
the slot's state in place (``apex_kda_decode``).  Layers of one kind
and one FFN form a stack; the pattern is a static list of SEGMENTS
(runs of consecutive layers of one stack, :attr:`MLAMoEConfig
.segments`), one ``lax.scan`` each; the cache is the carry of all of
them and a layer's index into its kind's cache rides to the kernels as
a scalar, as in ``gpt.forward_decode``.

Parameters are born in ``param_dtype`` (bf16 for serving: nothing is
cast per step; from another dtype, ``MLAMoEServed.serving_params``
casts the matrices once); matrices are stored ``(in, out)``.  Tensor-parallel
and training variants do not exist yet (ROADMAP, Queue 2); the
multi-token-prediction module of the published checkpoints is not
part of the model's logits and is not built.
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

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
#: a model with KDA layers counts, fourth, the state updates of its
#: decode steps: active slots x KDA layers
KDA_COUNTER = "kda_state_updates"

#: stack name by (mixer kind, FFN form)
_STACKS = {("mla", "dense"): "dense", ("mla", "moe"): "moe",
           ("kda", "dense"): "kda_dense", ("kda", "moe"): "kda_moe"}


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
    q_lora_rank: Optional[int] = 1536
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
    #: False: no rotation of the ``qk_rope_head_dim`` slice at all
    use_rope: bool = True
    #: the mixer of every layer, "mla" or "kda"; None: MLA everywhere
    layer_kinds: Optional[Tuple[str, ...]] = None
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if self.held.stop > self.n_routed_experts or len(self.held) < 1:
            raise ValueError(f"held {self.held} outside the router's "
                             f"{self.n_routed_experts} experts")
        kinds = self.layer_kinds
        if kinds is not None and (len(kinds) != self.num_layers
                                  or set(kinds) - {"mla", "kda"}):
            raise ValueError(f"layer_kinds {kinds} must name 'mla' or "
                             f"'kda' for each of {self.num_layers} layers")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "MLAMoEConfig":
        """From a published ``config.json`` dict (the keys a
        configuration file keeps verbatim): ``deepseek_v3``'s names, or
        ``kimi_linear``'s where they differ (``num_experts``,
        ``num_experts_per_token``, ``num_shared_experts``,
        ``num_expert_group``; ``linear_attn_config`` with its 1-based
        ``kda_layers``; ``mla_use_nope``).  ``q_lora_rank: null`` is one
        query matrix, ``rope_scaling: null`` plain frequencies.  All the
        experts the config counts are held; one chip's share of a wider
        router is the caller's to say, by overriding
        ``n_routed_experts`` (the router's width) together with
        ``held_start``/``held_count``.  Any field may be overridden."""
        def key(*names, default=None):
            for n in names:
                if conf.get(n) is not None:
                    return conf[n]
            if default is None:
                raise KeyError(f"config has none of {names}")
            return default

        rs = conf.get("rope_scaling") or {}
        dense = int(conf["first_k_dense_replace"])
        layers = int(conf["num_hidden_layers"])
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            num_dense_layers=dense, num_moe_layers=layers - dense,
            num_attention_heads=conf["num_attention_heads"],
            q_lora_rank=conf.get("q_lora_rank"),
            kv_lora_rank=conf["kv_lora_rank"],
            qk_nope_head_dim=conf["qk_nope_head_dim"],
            qk_rope_head_dim=conf["qk_rope_head_dim"],
            v_head_dim=conf["v_head_dim"],
            intermediate_size=conf["intermediate_size"],
            moe_intermediate_size=conf["moe_intermediate_size"],
            n_routed_experts=key("n_routed_experts", "num_experts"),
            n_shared_experts=key("n_shared_experts", "num_shared_experts"),
            num_experts_per_tok=key("num_experts_per_tok",
                                    "num_experts_per_token"),
            n_group=key("n_group", "num_expert_group"),
            topk_group=conf["topk_group"],
            routed_scaling_factor=conf["routed_scaling_factor"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=float(conf["rope_theta"]),
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max_position=int(rs.get(
                "original_max_position_embeddings",
                key("max_position_embeddings", "model_max_length"))),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            max_position_embeddings=key("max_position_embeddings",
                                        "model_max_length"),
            use_rope=not conf.get("mla_use_nope", False))
        lin = conf.get("linear_attn_config")
        if lin:
            kda = {int(i) for i in lin["kda_layers"]}
            kw.update(
                layer_kinds=tuple("kda" if i + 1 in kda else "mla"
                                  for i in range(layers)),
                kda_num_heads=lin["num_heads"],
                kda_head_dim=lin["head_dim"],
                kda_conv_size=lin["short_conv_kernel_size"])
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
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds or ("mla",) * self.num_layers

    def count(self, kind: str) -> int:
        """Layers whose mixer is ``kind``."""
        return self.kinds.count(kind)

    def stack_of(self, layer: int) -> str:
        """The parameter stack that holds layer ``layer`` (0-based):
        one a mixer kind and FFN form (:data:`_STACKS`)."""
        return _STACKS[self.kinds[layer], "dense"
                       if layer < self.num_dense_layers else "moe"]

    @property
    def segments(self) -> Tuple[tuple, ...]:
        """The layer pattern as runs of consecutive layers of one stack:
        ``(stack, kind, start, n, first)`` — ``n`` layers from
        ``start`` in the stack ``stack`` (:data:`_STACKS`), whose mixer
        is ``kind`` and whose first layer is number ``first`` among the
        layers of that kind (its index in the kind's cache)."""
        out, used, seen = [], {}, {}
        for i, kind in enumerate(self.kinds):
            stack = self.stack_of(i)
            if out and out[-1][0] == stack:
                out[-1][3] += 1
            else:
                out.append([stack, kind, used.get(stack, 0), 1,
                            seen.get(kind, 0)])
            used[stack] = used.get(stack, 0) + 1
            seen[kind] = seen.get(kind, 0) + 1
        return tuple(tuple(x) for x in out)

    @property
    def kda_width(self) -> int:
        """The KDA projections' width: heads x head size."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def kda_conv_shape(self) -> Tuple[int]:
        """A slot's convolution tail of one layer: the ``conv - 1`` last
        inputs of the three convolutions, oldest first, each ``3 *
        kda_width`` values (q, k, v side by side), as ONE row."""
        return ((self.kda_conv_size - 1) * 3 * self.kda_width,)

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
def _mixer_shapes(c: MLAMoEConfig, kind: str):
    H = c.hidden_size
    if kind == "kda":
        P, d = c.kda_width, c.kda_head_dim
        return {
            "attn_norm": (H,), "wqkv": (H, 3 * P),
            "conv_w": (c.kda_conv_size, 3 * P),
            "wf_a": (H, d), "wf_b": (d, P), "dt_bias": (P,),
            "a_log": (c.kda_num_heads,), "wb": (H, c.kda_num_heads),
            "wg_a": (H, d), "wg_b": (d, P), "o_norm": (d,),
            "wo": (P, H), "ffn_norm": (H,),
        }
    heads = c.num_attention_heads
    query = ({"wq": (H, heads, c.qk_head_dim)} if c.q_lora_rank is None
             else {"wq_a": (H, c.q_lora_rank), "q_norm": (c.q_lora_rank,),
                   "wq_b": (c.q_lora_rank, heads, c.qk_head_dim)})
    return {
        "attn_norm": (H,), **query,
        "wkv_a": (H, c.latent_width), "kv_norm": (c.kv_lora_rank,),
        "wkv_b_k": (c.kv_lora_rank, heads, c.qk_nope_head_dim),
        "wkv_b_v": (c.kv_lora_rank, heads, c.v_head_dim),
        "wo": (heads * c.v_head_dim, H), "ffn_norm": (H,),
    }


#: leaves kept in float32 whatever ``param_dtype`` (the router's bias,
#: the decay's two per-channel constants)
FLOAT32_LEAVES = ("router_bias", "dt_bias", "a_log")


def param_shapes(c: MLAMoEConfig) -> Dict:
    """The parameter tree as shapes: ``embed``/``head`` (V, H),
    ``final_norm``, and one stack with a leading layer axis for each
    (mixer kind, FFN form) the pattern has: ``dense`` and ``moe`` (MLA
    layers), ``kda_dense`` and ``kda_moe``.  Norm gains end in
    ``norm``; :data:`FLOAT32_LEAVES` are float32, all else
    ``param_dtype``."""
    H, F, Fe = c.hidden_size, c.intermediate_size, c.moe_intermediate_size
    n_held, Fs = len(c.held), c.moe_intermediate_size * c.n_shared_experts
    ffn = {
        "dense": dict(w_gate=(H, F), w_up=(H, F), w_down=(F, H)),
        "moe": dict(router=(H, c.n_routed_experts),
                    router_bias=(c.n_routed_experts,),
                    we_gate=(n_held, H, Fe), we_up=(n_held, H, Fe),
                    we_down=(n_held, Fe, H),
                    ws_gate=(H, Fs), ws_up=(H, Fs), ws_down=(Fs, H)),
    }
    out = {"embed": (c.vocab_size, H), "head": (c.vocab_size, H),
           "final_norm": (H,)}
    for (kind, form), stack in _STACKS.items():
        n = sum(seg[3] for seg in c.segments if seg[0] == stack)
        if n or kind == "mla":      # the MLA stacks exist, even empty
            leaves = dict(_mixer_shapes(c, kind), **ffn[form])
            out[stack] = {k: (n,) + v for k, v in leaves.items()}
    return out


def init_params(config: MLAMoEConfig, key, std: float = 0.02) -> Dict:
    """Seeded parameters in ``param_dtype``: matrices N(0, std), gains
    1 + N(0, std), the router's bias N(0, std / 2) in float32, a KDA
    layer's ``a_log`` and ``dt_bias`` N(0, std) in float32 (a decay
    near one half a token)."""
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
        elif name in FLOAT32_LEAVES:
            out.append(x)
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
    (T, ..., d); ``positions``: (T,).  Without ``use_rope`` ``x``
    itself."""
    if not c.use_rope:
        return x
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
    the rotated shared key: exactly the column that is cached.  The
    query comes through ``wq_a``/``wq_b`` or, where the layer has no
    bottleneck, through the one matrix ``wq``."""
    cd = c.compute_dtype
    if "wq" in p:
        q = jnp.einsum("th,hnd->tnd", x, p["wq"].astype(cd))
    else:
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


def _block(x, p, c: MLAMoEConfig, mix, token_mask, impl="auto"):
    """One layer on (T, H) activations; ``mix(h, p)`` is the layer's
    mixer on the normed activations and returns the heads' outputs (T,
    heads, d) — what ``wo`` multiplies — with what it cached (an MLA
    layer's latent columns or the pool it wrote them into; a KDA
    layer's state and convolution tail).  A dense layer's ``p`` has
    ``w_gate``, an expert layer's ``router``.  Returns ``(x, counts or
    None, cached)``."""
    cd = c.compute_dtype
    o, cached = mix(_rms_norm(x, p["attn_norm"], c.rms_norm_eps), p)
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
    head from the latent, causal flash attention.  The flash forward
    has one head size: values narrower than ``qk_nope + qk_rope`` ride
    zero-padded to it and the output is cut back (exact)."""
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
        narrow = q.shape[-1] - v.shape[-1]
        if narrow < 0:
            raise NotImplementedError(
                f"v_head_dim {v.shape[-1]} above the keys' "
                f"{q.shape[-1]}: the flash forward has one head size and "
                "only values can be padded to it")
        v = jnp.pad(v, ((0, 0), (0, 0), (0, narrow)))

        def bhsd(t):                      # (B*S, heads, d) -> (B, heads, S, d)
            return t.reshape(batch, T // batch, heads, -1) \
                .transpose(0, 2, 1, 3)

        o = flash_attention(bhsd(q), bhsd(k), bhsd(v), causal=True,
                            softmax_scale=c.softmax_scale, impl=flash)
        o = o.transpose(0, 2, 1, 3).reshape(T, heads, -1)
        return o[..., :c.v_head_dim], latent

    return attend


# --------------------------------------------------------------------- KDA
def _kda_inputs(h, y, p, c: MLAMoEConfig):
    """A KDA layer's recurrence inputs, float32.  ``h``: (T, H) normed
    activations; ``y``: (T, 3 * width) the short convolution's output
    (before its SiLU), q, k and v side by side.  Per head of size
    ``d``: ``q`` and ``k`` are SiLU'd, L2-normalised (eps 1e-6 under
    the root) and ``q`` scaled by ``d^-1/2``; ``v`` is SiLU'd; the
    log-decay, one a head and key channel, is ``g = -exp(a_log[head]) *
    softplus((h wf_a) wf_b + dt_bias)``; the write strength ``beta =
    sigmoid(h wb)``.  Returns ``(q, k, v, g, beta)``."""
    cd = c.compute_dtype
    T, heads, d = h.shape[0], c.kda_num_heads, c.kda_head_dim
    q, k, v = (x.reshape(T, heads, d) for x in jnp.split(
        jax.nn.silu(y.astype(jnp.float32)), 3, axis=-1))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    low = jnp.matmul(jnp.matmul(h, p["wf_a"].astype(cd)),
                     p["wf_b"].astype(cd))
    g = -jnp.exp(p["a_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(low.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32)
                          ).reshape(T, heads, d)
    beta = jax.nn.sigmoid(jnp.matmul(h, p["wb"].astype(cd))
                          .astype(jnp.float32))
    return unit(q) * d ** -0.5, unit(k), v, g, beta


def _kda_output(o, h, p, c: MLAMoEConfig):
    """The heads' outputs as ``wo`` takes them: ``o`` (T, heads, d)
    float32, RMS-normed a head (gain ``o_norm``) and gated by
    ``sigmoid((h wg_a) wg_b)``."""
    cd = c.compute_dtype
    gate = jnp.matmul(jnp.matmul(h, p["wg_a"].astype(cd)),
                      p["wg_b"].astype(cd)).astype(jnp.float32)
    o = _rms_norm(o, p["o_norm"], c.rms_norm_eps)
    return (o * jax.nn.sigmoid(gate).reshape(o.shape)).astype(cd)


def _kda_full(c: MLAMoEConfig, batch: int, token_mask, impl: str):
    """The whole-sequence KDA mixer: causal convolution over each
    sequence, the chunked delta rule from a zero state
    (:func:`apex_tpu.ops.kda.kda_chunked`).  ``token_mask`` (B, S), a
    PREFIX of each row: positions past it leave the state untouched
    (``beta = 0``, ``g = 0``) and the convolution's tail is taken at
    the mask's end.  Caches ``(state (B, heads, d, d) float32, tail (B,)
    + kda_conv_shape)``."""
    from apex_tpu.ops.kda import kda_chunked

    K, heads, d = c.kda_conv_size, c.kda_num_heads, c.kda_head_dim
    impl = {"auto": "auto", "pallas": "pallas",
            "interpret": "interpret"}.get(impl, "xla")

    def mix(h, p):
        cd = c.compute_dtype
        S = h.shape[0] // batch
        x3 = jnp.matmul(h, p["wqkv"].astype(cd)).reshape(batch, S, -1)
        xp = jnp.pad(x3.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
        w = p["conv_w"].astype(jnp.float32)
        y = sum(w[j] * xp[:, j:j + S] for j in range(K))
        q, k, v, g, beta = _kda_inputs(h, y.reshape(batch * S, -1), p, c)
        length = jnp.full((batch,), S, jnp.int32)
        if token_mask is not None:
            keep = token_mask.reshape(batch * S)
            g, beta = g * keep[:, None, None], beta * keep[:, None]
            length = jnp.sum(token_mask, axis=1).astype(jnp.int32)
        seq = lambda t: t.reshape((batch, S) + t.shape[1:])
        o, state = jax.vmap(lambda *a: kda_chunked(
            *a, jnp.zeros((heads, d, d), jnp.float32), impl=impl))(
                seq(q), seq(k), seq(v), seq(g), seq(beta))
        at = length[:, None] - (K - 1) + jnp.arange(K - 1)[None]  # (B,K-1)
        tail = jnp.take_along_axis(x3, jnp.clip(at, 0, S - 1)[:, :, None],
                                   axis=1)
        tail = jnp.where((at >= 0)[:, :, None], tail, 0)
        return _kda_output(o.reshape(batch * S, heads, d), h, p, c), \
            (state, tail.reshape((batch,) + c.kda_conv_shape))

    return mix


def _embed(params, tokens, c: MLAMoEConfig):
    return jnp.take(params["embed"], tokens, axis=0).astype(c.compute_dtype)


def _scan_segments(params, c: MLAMoEConfig, body, carry):
    """Run ``body(carry, p, index, kind)`` over the layers, one
    ``lax.scan`` a segment of the pattern (:attr:`MLAMoEConfig
    .segments`): ``p`` the layer's parameters, ``index`` its number
    among the layers of its mixer ``kind``.  The held experts' weights
    are NOT sliced: the body sees them whole, with ``expert_layer`` the
    layer's index among them (a slice of a kernel's operand would be
    copied out first; ``held_experts_ffn``).  A segment that is its
    whole stack scans over the stacked leaves; a part of a stack
    indexes them layer by layer, so that no part of a stack is ever cut
    out and copied.  Returns ``(carry, ys)``, ``ys`` a segment's
    stacked second results."""
    ys = []
    for stack, kind, start, n, first in c.segments:
        whole = {k: v for k, v in params[stack].items()
                 if k in EXPERT_LEAVES}
        rest = {k: v for k, v in params[stack].items() if k not in whole}
        covers = (start == 0
                  and n == next(iter(rest.values())).shape[0])

        def step(carry, inp, whole=whole, rest=rest, covers=covers,
                 start=start, first=first, kind=kind):
            i = inp[-1]
            p = inp[0] if covers else {
                k: jax.lax.dynamic_index_in_dim(v, start + i, 0,
                                                keepdims=False)
                for k, v in rest.items()}
            p = dict(p, **whole)
            if whole:
                p["expert_layer"] = start + i
            return body(carry, p, first + i, kind)

        ix = jnp.arange(n, dtype=jnp.int32)
        carry, y = jax.lax.scan(step, carry,
                                (rest, ix) if covers else (ix,))
        ys.append((kind, y))
    return carry, ys


def forward(params, tokens, config: MLAMoEConfig, attn_impl: str = "auto",
            return_hidden: bool = False, return_cache: bool = False,
            token_mask=None):
    """Full forward of (B, S) ``tokens``.  Returns float32 logits (B, S,
    V), or with ``return_hidden`` the final-normed activations (B, S,
    H); with ``return_cache`` also what the layers cache, by cache
    name: ``latent`` (MLA layers, B, S, latent_width) and, where the
    model has KDA layers, ``kda_state`` (KDA layers, B, heads, d, d)
    and ``kda_conv`` ((KDA layers, B) + kda_conv_shape) at each
    sequence's end.  ``token_mask`` (B, S), a prefix of each row:
    padding routes to no expert and leaves a KDA state untouched."""
    c = config
    B, S = tokens.shape
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    attend = _attend_full(c, B, attn_impl)
    mixers = {
        "mla": lambda h, p: attend(*mla_project(h, p, c, positions), p),
        "kda": _kda_full(c, B, token_mask, attn_impl),
    }
    mask = None if token_mask is None else token_mask.reshape(B * S)

    def body(x, p, index, kind):
        x, _, cached = _block(x, p, c, mixers[kind], mask, attn_impl)
        return x, cached

    x, ys = _scan_segments(params, c, body,
                           _embed(params, tokens.reshape(B * S), c))
    x = _rms_norm(x, params["final_norm"], c.rms_norm_eps)
    out = x.reshape(B, S, -1)
    if not return_hidden:
        out = jnp.matmul(out.astype(jnp.float32),
                         params["head"].T.astype(jnp.float32))
    if not return_cache:
        return out
    cache = {}
    latents = [y for kind, y in ys if kind == "mla"]
    if latents:
        cache["latent"] = jnp.concatenate(latents, axis=0).reshape(
            -1, B, S, c.latent_width)
    kda = [y for kind, y in ys if kind == "kda"]
    if kda:
        cache["kda_state"] = jnp.concatenate([y[0] for y in kda], axis=0)
        cache["kda_conv"] = jnp.concatenate([y[1] for y in kda], axis=0)
    return out, cache


def forward_decode(params, tokens, positions, active, pools, page_tables,
                   config: MLAMoEConfig, attn_impl: str = "auto",
                   verify_width: int = 1, write_mask=None):
    """One token a slot over the cache (the contract of
    :func:`apex_tpu.models.gpt.forward_decode`).

    ``pools``: the carried cache state — ``"latent"``, the (MLA layers,
    num_pages, 1, latent_width, page_size) pool; where the model has
    KDA layers ``"kda_state"`` and ``"kda_conv"``, per-slot (KDA
    layers, slots + 1, ...); and optionally ``"counters"``, an int32
    vector in ``counter_names``' order that this step adds to.  An MLA
    layer writes its tokens' latent columns in place
    (``apex_kv_write``) and attends in absorbed form
    (``apex_mla_decode_attention``): the query takes the key
    up-projection, the output the value up-projection.  A KDA layer
    shifts the slot's convolution tail and updates its state in place
    (``apex_kda_decode``); an inactive slot's are left as they were.
    Returns ``(hidden (B, H), pools)``, hidden final-normed.
    """
    from apex_tpu.inference.kv_cache import COUNTERS, write_decode_pools
    from apex_tpu.ops.kda import conv_step, kda_decode
    from apex_tpu.ops.mla_decode_pallas import mla_decode_attention

    c = config
    if verify_width != 1:
        raise NotImplementedError(
            "the latent decode kernel and the KDA state update take one "
            "position a slot: speculative verify and chunked prefill "
            "are not built for this family (ROADMAP, Queue 2)")
    cd = c.compute_dtype
    B = tokens.shape[0]
    positions = positions.astype(jnp.int32)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    if write_mask is None:
        write_mask = active
    names = [n for n in ("latent", "kda_state", "kda_conv") if n in pools]

    def body(carry, p, index, kind):
        x, cache, counters = carry

        def attend(h, p_):
            q_nope, q_rope, latent = mla_project(h, p_, c, positions)
            (pool,) = write_decode_pools(
                (cache["latent"],), (latent[:, None],), page_tables,
                positions, write_mask, layer=index, impl=attn_impl)
            q_lat = jnp.einsum("thd,chd->thc", q_nope,
                               p_["wkv_b_k"].astype(cd))
            o_lat = mla_decode_attention(
                jnp.concatenate([q_lat, q_rope], -1), pool, page_tables,
                lengths, c.kv_lora_rank, c.softmax_scale, impl=attn_impl,
                layer=index)
            return jnp.einsum("thc,chd->thd", o_lat.astype(cd),
                              p_["wkv_b_v"].astype(cd)), \
                dict(cache, latent=pool)

        def recur(h, p_):
            y, conv = conv_step(
                jnp.matmul(h, p_["wqkv"].astype(cd)), p_["conv_w"],
                cache["kda_conv"], active, index, impl=attn_impl)
            o, state = kda_decode(
                *_kda_inputs(h, y, p_, c), cache["kda_state"], active,
                index, impl=attn_impl)
            return _kda_output(o, h, p_, c), \
                dict(cache, kda_state=state, kda_conv=conv)

        x, counts, cache = _block(
            x, p, c, recur if kind == "kda" else attend, active, attn_impl)
        if counters is not None:
            add = [jnp.int32(0)] * counters.shape[0]
            if counts is not None:
                add[:3] = [counts["assignments_held"],
                           counts["assignments_all"], counts["experts_hit"]]
            if kind == "kda":
                add[3] = jnp.sum(active, dtype=jnp.int32)
            counters = counters + jnp.stack(add).astype(counters.dtype)
        return (x, cache, counters), None

    (x, cache, counters), _ = _scan_segments(
        params, c, body,
        (_embed(params, tokens, c), {n: pools[n] for n in names},
         pools.get(COUNTERS)))
    out = dict(pools, **cache)
    if counters is not None:
        out[COUNTERS] = counters
    return _rms_norm(x, params["final_norm"], c.rms_norm_eps), out


# ----------------------------------------------------------- served model
class MLAMoEServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md)."""

    #: one position a slot a step: no speculative verify, no chunks
    multi_position = False
    #: rotary positions: no learned table bounds a request
    max_positions = None
    #: the leaves of the stacks that :func:`forward` and
    #: :func:`forward_decode` read ONLY as ``leaf.astype(compute_dtype)``
    #: (``mla_project``, the attention cores, the KDA projections,
    #: ``_block``, ``_gated_ffn``): the mixers' projections, the dense
    #: FFN and the shared expert.  Not the router and its bias
    #: (float32), not a norm gain, ``a_log``, ``dt_bias`` or the short
    #: convolution's filter (float32 arithmetic), not the held experts'
    #: ``we_*`` (the grouped matmul reads them as they are stored), not
    #: ``embed``/``head``
    cast_once_leaves = ("wq", "wq_a", "wq_b", "wkv_a", "wkv_b_k", "wkv_b_v",
                        "wo", "wqkv", "wf_a", "wf_b", "wb", "wg_a", "wg_b",
                        "w_gate", "w_up", "w_down",
                        "ws_gate", "ws_up", "ws_down")

    def __init__(self, config: MLAMoEConfig):
        self.config = config

    @property
    def counter_names(self):
        return COUNTER_NAMES + ((KDA_COUNTER,) if self.config.count("kda")
                                else ())

    def cache_spec(self) -> Dict[str, tuple]:
        """``latent``: the paged pool of the MLA layers; with KDA
        layers, per-slot ``kda_state`` (float32) and ``kda_conv`` (the
        compute dtype)."""
        from apex_tpu.inference.kv_cache import PerSlot

        c = self.config
        spec = {"latent": (c.count("mla"), 1, c.latent_width)}
        if c.count("kda"):
            spec["kda_state"] = PerSlot(
                c.count("kda"),
                (c.kda_num_heads, c.kda_head_dim, c.kda_head_dim),
                jnp.float32)
            spec["kda_conv"] = PerSlot(c.count("kda"), c.kda_conv_shape,
                                       c.compute_dtype)
        return spec

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
        """(1, S) padded prompt -> final-normed hidden (S, 1, H) and
        what to cache, by name: a paged pool's columns (L, S, heads,
        dim), a per-slot entry's values at ``prompt_len`` (L, ...)."""
        S = prompt.shape[1]
        hidden, cache = forward(
            params, prompt, self.config, attn_impl=attn_impl,
            return_hidden=True, return_cache=True,
            token_mask=jnp.arange(S, dtype=jnp.int32)[None] < prompt_len)
        out = {name: (x.transpose(0, 2, 1, 3) if name == "latent"
                      else x[:, 0]) for name, x in cache.items()}
        return hidden.transpose(1, 0, 2), out

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)
