"""LFM2-MoE: a decoder whose layers mix by a GATED SHORT CONVOLUTION or
by grouped-query attention, over a dense or a sparse feed-forward,
served.

The seventh served family (docs/inference.md), and a file of its own: it
shares :mod:`apex_tpu.ops`, :mod:`apex_tpu.inference` and the held-expert
layer (:func:`apex_tpu.transformer.expert_parallel.held_experts_ffn`)
with the other families and no block code (ROADMAP D6).  What is
different from all of them:

- **a convolution is a layer's ONLY mixer** (``layer_types``: ``conv``):
  the normed input is projected to three parts ``B | C | x``; ``z = B *
  x`` runs through a causal depthwise convolution of ``conv_L_cache``
  taps with no bias and no activation, and ``C`` gates its sum.  Such a
  layer keeps NO keys and values: all a sequence carries of it is the
  last ``conv_L_cache - 1`` rows of ``z``, rows of a decode slot
  (:class:`apex_tpu.inference.kv_cache.PerSlot`, ``conv_tail``), shifted
  in place by ``apex_kda_conv_step``;
- **the other layers** (``full_attention``) are grouped-query attention
  with an RMSNorm a head on queries and keys before the rotation, their
  keys and values columns of the paged pools ``k`` and ``v``: the pools
  have as many layers as the model has ATTENTION layers, the tails as
  many as it has convolution layers;
- **a sigmoid router with a choice-only bias over every expert**
  (``route_group_limited`` with one group and ``eps`` 1e-6), no shared
  expert; the first ``num_dense_layers`` layers have a dense gated
  feed-forward instead.  This process holds EVERY expert of a layer;
- **embedding and head are tied**: the sampling head multiplies by the
  embedding.

The layer (``h`` the stream, every norm an RMSNorm with a gain)::

    u = norm(h; operator_norm)
    conv:  [B | C | x] = u W_in;  z = B * x
           y_t = sum_j w[j] z_{t - K + 1 + j};  m = (C * y) W_out
    attn:  q, k, v = u Wq, u Wk, u Wv;  q, k = norm(q), norm(k) a head
           m = softmax(rope(q) rope(k)^T / sqrt(d), causal) v Wo
    h = h + m;  f = norm(h; ffn_norm)
    dense: h = h + (silu(f W1) * (f W3)) W2
    moe:   s = sigmoid(f Wr);  chosen = the top_k of s + bias
           g_e = s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor
           h = h + sum_{e chosen and held} g_e (silu(f W1_e) * (f W3_e)) W2_e

and ``logits = norm(h; final_norm) E^T``.  Norm gains, the router, its
bias, the convolution's filter and its sum are float32; matrices,
activations, cached keys and values and the tail the compute dtype.

**The layer loop.**  The pattern of (mixer, feed-forward) kinds is cut
into a PREFIX, a PERIOD that repeats, and a SUFFIX
(:attr:`LFM2MoEConfig.plan`: the longest repetition the pattern holds).
Prefix and suffix layers are unrolled, each with parameters of its own;
the period is ONE ``lax.scan`` over its repeats whose body holds the
period's layers in turn, so a program compiles five layer bodies for
the 13 layers of the benchmark's stage (one leading dense layer, three
periods of ``conv, attention, conv, conv``) and ten for the published
24.  The pools and the tails are the carry; each is written in place
through an aliased kernel.  A period position's experts are not sliced
by the scan (the grouped matmul takes the stack and the repeat's index).
Tensor-parallel and training variants do not exist; a multi-position
update of the tail (speculative verify, chunked prefill) and tail
snapshots (prefix sharing) are ROADMAP, Queue 2.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.rope import apply_rope, apply_rope_at
from apex_tpu.transformer.expert_parallel import held_experts_ffn

__all__ = ["COUNTER_NAMES", "EXPERT_LEAVES", "FLOAT32_LEAVES",
           "LFM2MoEConfig", "LFM2MoEServed", "forward", "forward_decode",
           "init_params", "param_shapes"]

#: the device-side counters of the decode step, in the order of the
#: carried vector: tail updates (active slots x convolution layers a
#: step) and the expert layer's three, summed over the layers, as the
#: latent family keeps them
COUNTER_NAMES = ("conv_state_updates", "moe_assignments_held",
                 "moe_assignments_all", "moe_experts_hit")
#: leaves kept in float32 whatever ``param_dtype``
FLOAT32_LEAVES = ("operator_norm", "ffn_norm", "q_norm", "k_norm",
                  "final_norm", "router", "router_bias", "conv_w")
#: the held experts' weights: the period's scan leaves them STACKED
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
#: the constant under the sum of the chosen scores
ROUTER_EPS = 1e-6
#: :func:`init_params`' scales of the tied embedding and the router's bias
EMBED_STD, BIAS_STD = 0.02, 0.01
_PUBLISHED_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class LFM2MoEConfig:
    """Shapes and constants under the published config's names."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    #: the mixer of every layer: "conv" or "full_attention"
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: the convolution's taps
    conv_L_cache: int = 3
    #: the router's width and the experts held: ALL the experts of a layer
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types {kinds} must name 'conv' or 'full_attention' "
                f"for each of {self.num_hidden_layers} layers")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide the width and their groups")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers outside the layers")
        if self.conv_L_cache < 2:
            raise ValueError("a convolution of one tap keeps no tail")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "LFM2MoEConfig":
        """From a published ``config.json`` dict (``model_type:
        lfm2_moe``).  The keys that pick the mechanism are held to what
        this file implements: no bias in the convolution, the chosen
        experts' weights renormalised, a choice-only bias, tied
        embedding and head.  All the experts the config counts are held.
        Any field may be overridden."""
        want = {"model_type": "lfm2_moe", "conv_bias": False,
                "norm_topk_prob": True, "use_expert_bias": True,
                "tie_word_embeddings": True}
        for key, value in want.items():
            if conf.get(key, value) != value:
                raise ValueError(
                    f"config {key} = {conf[key]!r}: this file serves "
                    f"{key} = {value!r}")
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            intermediate_size=conf["intermediate_size"],
            moe_intermediate_size=conf["moe_intermediate_size"],
            num_hidden_layers=conf["num_hidden_layers"],
            num_dense_layers=conf["num_dense_layers"],
            layer_types=tuple(conf["layer_types"]),
            num_attention_heads=conf["num_attention_heads"],
            num_key_value_heads=conf["num_key_value_heads"],
            conv_L_cache=conf["conv_L_cache"],
            num_experts=conf["num_experts"],
            num_experts_per_tok=conf["num_experts_per_tok"],
            routed_scaling_factor=float(conf["routed_scaling_factor"]),
            norm_eps=conf["norm_eps"], rope_theta=float(conf["rope_theta"]),
            max_position_embeddings=conf["max_position_embeddings"])
        kw.update(overrides)
        return cls(**kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """``(mixer, feed-forward)`` of every layer: ``conv`` or
        ``attn``, ``dense`` or ``moe``."""
        return tuple(("conv" if t == "conv" else "attn",
                      "dense" if i < self.num_dense_layers else "moe")
                     for i, t in enumerate(self.layer_types))

    def count(self, mixer: str) -> int:
        """Layers whose mixer is ``mixer`` (``conv`` or ``attn``)."""
        return sum(1 for m, _ in self.kinds if m == mixer)

    @property
    def plan(self) -> Tuple[tuple, tuple, int, tuple]:
        """``(prefix, period, repeats, suffix)``: the layers' kinds as
        ``prefix + period * repeats + suffix``, the repetition that
        covers most layers (ties: the shorter period, then the earlier
        start); without one, every layer is prefix."""
        kinds, L = self.kinds, self.num_hidden_layers
        best = (0, 0, 0, 0)             # covered, -period, -start, repeats
        for start in range(L):
            for p in range(1, (L - start) // 2 + 1):
                n = 1
                while start + (n + 1) * p <= L and \
                        kinds[start + n * p:start + (n + 1) * p] \
                        == kinds[start:start + p]:
                    n += 1
                if n >= 2:
                    best = max(best, (n * p, -p, -start, n))
        covered, p, start, n = best[0], -best[1], -best[2], best[3]
        if not covered:
            return kinds, (), 0, ()
        return (kinds[:start], kinds[start:start + p], n,
                kinds[start + covered:])

    @property
    def tail_shape(self) -> Tuple[int]:
        """A slot's convolution tail of one layer: the ``conv_L_cache -
        1`` last rows of ``z``, oldest first, side by side as ONE row
        (what ``apex_kda_conv_step`` takes)."""
        return ((self.conv_L_cache - 1) * self.hidden_size,)

    def served_model(self) -> "LFM2MoEServed":
        return LFM2MoEServed(self)


# ------------------------------------------------------------- parameters
def _layer_shapes(c: LFM2MoEConfig, kind) -> Dict:
    mixer, ffn = kind
    H, d = c.hidden_size, c.head_dim
    out = {"operator_norm": (H,), "ffn_norm": (H,)}
    if mixer == "conv":
        out.update(w_in=(H, 3 * H), conv_w=(c.conv_L_cache, H),
                   w_out=(H, H))
    else:
        nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
        out.update(wqkv=(H, nq + 2 * nkv), q_norm=(d,), k_norm=(d,),
                   wo=(nq, H))
    if ffn == "dense":
        F = c.intermediate_size
        out.update(w1=(H, F), w3=(H, F), w2=(F, H))
    else:
        F, n = c.moe_intermediate_size, c.num_experts
        out.update(router=(H, c.num_experts), router_bias=(c.num_experts,),
                   we_gate=(n, H, F), we_up=(n, H, F), we_down=(n, F, H))
    return out


def param_shapes(c: LFM2MoEConfig) -> Dict:
    """The parameter tree's shapes, laid out as the layer loop walks it
    (:attr:`LFM2MoEConfig.plan`): ``prefix`` and ``suffix`` a list of
    layers, each a dict of its own leaves; ``period`` a list of the
    period's positions, each a dict of leaves STACKED over the repeats
    on a leading axis.  Matrices input-major; ``w_in`` the three parts
    ``B | C | x`` side by side, ``wqkv`` queries, keys and values;
    ``conv_w`` ``(taps, channels)``, ``conv_w[taps - 1]`` the current
    input's; the experts' ``we_*`` ``(experts, ...)`` in id order.
    There is no ``head``: the embedding is it."""
    prefix, period, n, suffix = c.plan
    return {
        "embed": (c.vocab_size, c.hidden_size),
        "final_norm": (c.hidden_size,),
        "prefix": [_layer_shapes(c, k) for k in prefix],
        "period": [{name: (n,) + shape
                    for name, shape in _layer_shapes(c, k).items()}
                   for k in period],
        "suffix": [_layer_shapes(c, k) for k in suffix],
    }


def init_params(config: LFM2MoEConfig, key) -> Dict:
    """Seeded parameters: the embedding ``N(0, EMBED_STD)``, small against
    the stream (the head is TIED: at unit variance the stream's own copy
    of the current token's embedding would outscore every other row and
    each step would repeat its input); a matrix ``N(0, 1 / fan_in)``
    (unit variance in, unit variance out, so that every mixer and
    feed-forward moves the stream); the filter ``N(0, 1 / taps)``; the
    router ``N(0, 1 / fan_in)``, its bias ``N(0, BIAS_STD)``; gains ``1
    + N(0, 0.02)``, but the final norm's ``(1 + N(0, 0.02)) / (EMBED_STD
    sqrt(H))``: logits of unit variance.  :data:`FLOAT32_LEAVES`
    float32, all else ``param_dtype``."""
    c = config
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm"):
            x = 1.0 + 0.02 * x
            if name == "final_norm":
                x = x * c.hidden_size ** -0.5 / EMBED_STD
        elif name == "router_bias":
            x = BIAS_STD * x
        elif name == "embed":
            x = EMBED_STD * x
        else:
            x = x * shape[-2] ** -0.5
        out.append(x if name in FLOAT32_LEAVES else x.astype(c.param_dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ pieces
def _rms_norm(x, gain, eps):
    """RMSNorm in float32 over the last axis; the result in ``x``'s
    dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * gain.astype(jnp.float32)).astype(x.dtype)


def _embed(params, tokens, c: LFM2MoEConfig):
    return jnp.take(params["embed"], tokens, axis=0).astype(c.compute_dtype)


def _conv_inputs(u, p, c: LFM2MoEConfig):
    """(T, H) normed rows -> the convolution's input ``z = B * x`` (T,
    H) in the compute dtype (what the tail caches) and the gate ``C``
    (T, H) float32."""
    H = c.hidden_size
    bcx = jnp.matmul(u, p["w_in"].astype(c.compute_dtype)) \
        .astype(jnp.float32)
    z = bcx[:, :H] * bcx[:, 2 * H:]
    return z.astype(c.compute_dtype), bcx[:, H:2 * H]


def _conv_output(gate, y, p, c: LFM2MoEConfig):
    """The mixer's addition to the stream: the convolution's sum ``y``
    (T, H) float32, gated, through ``w_out``."""
    cd = c.compute_dtype
    return jnp.matmul((gate * y).astype(cd), p["w_out"].astype(cd))


def _qkv(u, p, c: LFM2MoEConfig):
    """(T, H) normed rows -> q (T, heads, d), k, v (T, kv heads, d),
    unrotated; q and k normed a head."""
    d = c.head_dim
    y = jnp.matmul(u, p["wqkv"].astype(c.compute_dtype))
    nq, nk = c.num_attention_heads * d, c.num_key_value_heads * d
    q = _rms_norm(y[:, :nq].reshape(-1, c.num_attention_heads, d),
                  p["q_norm"], c.norm_eps)
    k = _rms_norm(y[:, nq:nq + nk].reshape(-1, c.num_key_value_heads, d),
                  p["k_norm"], c.norm_eps)
    return q, k, y[:, nq + nk:].reshape(-1, c.num_key_value_heads, d)


def _attn_output(attn, p, c: LFM2MoEConfig):
    cd = c.compute_dtype
    return jnp.matmul(attn.reshape(attn.shape[0], -1).astype(cd),
                      p["wo"].astype(cd))


def _feed_forward(h, p, c: LFM2MoEConfig, token_mask, expert_layer, impl):
    """``h`` plus the layer's feed-forward over its normed rows: dense
    where ``p`` has ``w1``, else the held experts' routed part
    (``expert_layer``: the repeat whose experts these are where the
    expert leaves are a period's stack, else None).  Returns ``(h,
    counts or None)``."""
    cd = c.compute_dtype
    f = _rms_norm(h, p["ffn_norm"], c.norm_eps)
    if "w1" in p:
        inner = jax.nn.silu(jnp.matmul(f, p["w1"].astype(cd))) \
            * jnp.matmul(f, p["w3"].astype(cd))
        return h + jnp.matmul(inner, p["w2"].astype(cd)), None
    routed, counts = held_experts_ffn(
        f, p, range(c.num_experts), top_k=c.num_experts_per_tok, n_group=1, topk_group=1,
        scale=c.routed_scaling_factor, token_mask=token_mask,
        layer=expert_layer, eps=ROUTER_EPS,
        impl={"auto": "auto", "pallas": "pallas"}.get(impl, "xla"))
    return h + routed, counts


def _walk(params, c: LFM2MoEConfig, body, carry):
    """Run ``body(carry, p, kind, index, expert_layer) -> carry`` over
    the layers in order (:attr:`LFM2MoEConfig.plan`): ``p`` the layer's
    parameters, ``kind`` its ``(mixer, feed-forward)``, ``index`` its
    number among the layers of its mixer (its index in that mixer's
    cache).  Prefix and suffix layers are unrolled with
    ``expert_layer`` None; the period is one ``lax.scan`` over its
    repeats, a position's expert leaves handed over whole with
    ``expert_layer`` the repeat (a slice of a kernel's operand would be
    copied out first)."""
    prefix, period, n, suffix = c.plan
    seen = {"conv": 0, "attn": 0}

    def unrolled(carry, kinds, layers):
        for kind, p in zip(kinds, layers):
            carry = body(carry, p, kind, seen[kind[0]], None)
            seen[kind[0]] += 1
        return carry

    carry = unrolled(carry, prefix, params["prefix"])
    if n:
        first = dict(seen)
        each = {m: sum(1 for k in period if k[0] == m) for m in seen}
        whole = [{k: v for k, v in p.items() if k in EXPERT_LEAVES}
                 for p in params["period"]]
        rest = [{k: v for k, v in p.items() if k not in EXPERT_LEAVES}
                for p in params["period"]]

        def step(carry, inp):
            i, layers = inp
            at = {m: first[m] + i * each[m] for m in first}
            for kind, p, experts in zip(period, layers, whole):
                carry = body(carry, dict(p, **experts), kind, at[kind[0]],
                             i if experts else None)
                at[kind[0]] += 1
            return carry, None

        carry, _ = jax.lax.scan(
            step, carry, (jnp.arange(n, dtype=jnp.int32), rest))
        for m in seen:
            seen[m] += n * each[m]
    return unrolled(carry, suffix, params["suffix"])


def forward(params, tokens, config: LFM2MoEConfig, attn_impl: str = "auto",
            return_hidden: bool = False, return_cache: bool = False,
            token_mask=None):
    """Full forward of (B, S) ``tokens``.  Returns float32 logits (B, S,
    V), or with ``return_hidden`` the final-normed activations the head
    multiplies (B, S, H); with ``return_cache`` also what the layers
    cache, by cache name: ``k`` and ``v`` (attention layers, B, S, kv
    heads, d), the rotated keys and the values, and ``conv_tail``
    ((convolution layers, B) + tail_shape) at each sequence's end.
    ``token_mask`` (B, S), a PREFIX of each row: positions past it route
    to no expert and the tail is taken at the mask's end."""
    from apex_tpu.ops.attention import flash_attention

    c = config
    B, S = tokens.shape
    K, H = c.conv_L_cache, c.hidden_size
    flash = {"auto": "auto", "pallas": "pallas"}.get(attn_impl, "scan")
    positions = jnp.arange(S, dtype=jnp.int32)
    length = jnp.full((B,), S, jnp.int32) if token_mask is None \
        else jnp.sum(token_mask, axis=1).astype(jnp.int32)
    mask = None if token_mask is None else token_mask.reshape(B * S)
    # the rows of z a tail keeps: the K - 1 before each sequence's end
    at = length[:, None] - (K - 1) + jnp.arange(K - 1)[None]      # (B, K-1)
    kv = (c.count("attn"), B, S, c.num_key_value_heads, c.head_dim)
    cache = {"k": jnp.zeros(kv, c.compute_dtype),
             "v": jnp.zeros(kv, c.compute_dtype),
             "conv_tail": jnp.zeros((c.count("conv"), B) + c.tail_shape,
                                    c.compute_dtype)}

    put = jax.lax.dynamic_update_index_in_dim

    def body(carry, p, kind, index, expert_layer):
        h, cache = carry
        u = _rms_norm(h, p["operator_norm"], c.norm_eps)
        if kind[0] == "conv":
            z, gate = _conv_inputs(u, p, c)
            z3 = z.reshape(B, S, H)
            zp = jnp.pad(z3.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
            y = sum(p["conv_w"][j] * zp[:, j:j + S] for j in range(K))
            h = h + _conv_output(gate, y.reshape(B * S, H), p, c)
            if return_cache:
                tail = jnp.take_along_axis(
                    z3, jnp.clip(at, 0, S - 1)[:, :, None], axis=1)
                tail = jnp.where((at >= 0)[:, :, None], tail, 0)
                cache = dict(cache, conv_tail=put(
                    cache["conv_tail"], tail.reshape((B,) + c.tail_shape),
                    index, 0))
        else:
            q, k, v = _qkv(u, p, c)
            bhsd = lambda t: t.reshape((B, S) + t.shape[1:]) \
                .transpose(0, 2, 1, 3)
            q, k = apply_rope(bhsd(q), positions, c.rope_theta), \
                apply_rope(bhsd(k), positions, c.rope_theta)
            attn = flash_attention(q, k, bhsd(v), causal=True, impl=flash)
            attn = attn.transpose(0, 2, 1, 3).reshape(B * S, -1, c.head_dim)
            h = h + _attn_output(attn, p, c)
            if return_cache:
                cache = dict(
                    cache,
                    k=put(cache["k"], k.transpose(0, 2, 1, 3), index, 0),
                    v=put(cache["v"], v.reshape(kv[1:]), index, 0))
        h, _ = _feed_forward(h, p, c, mask, expert_layer, attn_impl)
        return h, cache

    h, cache = _walk(params, c, body,
                     (_embed(params, tokens.reshape(B * S), c), cache))
    out = _rms_norm(h, params["final_norm"], c.norm_eps).reshape(B, S, -1)
    if not return_hidden:
        out = jnp.matmul(out.astype(jnp.float32),
                         params["embed"].T.astype(jnp.float32))
    return (out, cache) if return_cache else out


def forward_decode(params, tokens, positions, active, pools, page_tables,
                   config: LFM2MoEConfig, attn_impl: str = "auto",
                   verify_width: int = 1, write_mask=None):
    """One token a slot over both caches (the contract of
    :func:`apex_tpu.models.gpt.forward_decode`).

    ``pools``: ``"k"`` and ``"v"``, (attention layers, pages, kv heads,
    d, page_size); ``"conv_tail"`` ((convolution layers, slots + 1) +
    tail_shape); and optionally ``"counters"``.  An attention layer
    writes the token's key and value into its pages (``apex_kv_write``)
    and attends over them, every group of query heads against its one
    key/value head (``apex_decode_attention``); a convolution layer
    shifts the slot's tail in place (``apex_kda_conv_step``); an inactive
    slot's are left as they were.  Returns ``(hidden (B, H), pools)``,
    hidden final-normed."""
    from apex_tpu.inference.kv_cache import COUNTERS, write_decode_pools
    from apex_tpu.ops.decode_attention_pallas import decode_attention
    from apex_tpu.ops.kda import conv_step

    c = config
    if verify_width != 1 or write_mask is not None:
        raise NotImplementedError(
            "the convolution's tail takes one position a slot a step and "
            "cannot be rolled back: speculative verify and chunked "
            "prefill are not built for this family (ROADMAP, Queue 2)")
    positions = positions.astype(jnp.int32)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)

    def body(carry, p, kind, index, expert_layer):
        h, k_pool, v_pool, tails, counted = carry
        u = _rms_norm(h, p["operator_norm"], c.norm_eps)
        if kind[0] == "conv":
            z, gate = _conv_inputs(u, p, c)
            y, tails = conv_step(z, p["conv_w"], tails, active, index,
                                 impl=attn_impl)
            h = h + _conv_output(gate, y, p, c)
        else:
            q, k, v = _qkv(u, p, c)
            q = apply_rope_at(q, positions, c.rope_theta)
            k = apply_rope_at(k, positions, c.rope_theta)
            k_pool, v_pool = write_decode_pools(
                (k_pool, v_pool), (k, v), page_tables, positions, active,
                layer=index, impl=attn_impl)
            attn = decode_attention(q, k_pool, v_pool, page_tables, lengths,
                                    impl=attn_impl, layer=index)
            h = h + _attn_output(attn, p, c)
        h, counts = _feed_forward(h, p, c, active, expert_layer, attn_impl)
        if counts is not None:
            counted = counted + jnp.stack(
                [counts["assignments_held"], counts["assignments_all"],
                 counts["experts_hit"]]).astype(jnp.int32)
        return h, k_pool, v_pool, tails, counted

    h, k_pool, v_pool, tails, counted = _walk(
        params, c, body,
        (_embed(params, tokens, c), pools["k"], pools["v"],
         pools["conv_tail"], jnp.zeros((3,), jnp.int32)))
    out = dict(pools, k=k_pool, v=v_pool, conv_tail=tails)
    if COUNTERS in pools:
        updates = c.count("conv") * jnp.sum(active, dtype=jnp.int32)
        out[COUNTERS] = pools[COUNTERS] + jnp.concatenate(
            [updates[None], counted])
    return _rms_norm(h, params["final_norm"], c.norm_eps), out


# ----------------------------------------------------------- served model
class LFM2MoEServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md)."""

    #: one position a slot a step: no speculative verify, no chunks
    multi_position = False
    #: rotary positions: no learned table bounds a request
    max_positions = None
    #: the leaves every served program reads only as
    #: ``leaf.astype(compute_dtype)``: the mixers' and the dense
    #: feed-forward's matrices.  Not :data:`FLOAT32_LEAVES`, not the
    #: held experts' ``we_*`` (the grouped matmul reads them as they are
    #: stored), not ``embed`` (gathered, and the sampling head's own)
    cast_once_leaves = ("w_in", "w_out", "wqkv", "wo", "w1", "w3", "w2")
    counter_names = COUNTER_NAMES

    def __init__(self, config: LFM2MoEConfig):
        self.config = config

    def cache_spec(self) -> Dict[str, tuple]:
        """``k`` and ``v`` paged over the ATTENTION layers, ``conv_tail``
        (the compute dtype) per slot over the CONVOLUTION layers."""
        from apex_tpu.inference.kv_cache import PerSlot

        c = self.config
        kv = (c.count("attn"), c.num_key_value_heads, c.head_dim)
        return {"k": kv, "v": kv,
                "conv_tail": PerSlot(c.count("conv"), c.tail_shape,
                                     c.compute_dtype)}

    def head(self, params):
        """Tied: the head's matrix is the embedding."""
        return params["embed"]

    def serving_params(self, params):
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt -> final-normed hidden (S, 1, H) and
        what to cache, by name: the paged pools' columns (attention
        layers, S, kv heads, d), the tails AT ``prompt_len`` (convolution
        layers, ...)."""
        S = prompt.shape[1]
        hidden, cache = forward(
            params, prompt, self.config, attn_impl=attn_impl,
            return_hidden=True, return_cache=True,
            token_mask=jnp.arange(S, dtype=jnp.int32)[None] < prompt_len)
        return hidden.transpose(1, 0, 2), {n: x[:, 0]
                                           for n, x in cache.items()}

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)
