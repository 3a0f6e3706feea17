"""The plain reference for a ``deepseek_v3``-style decoder: multi-head
latent attention (MLA) with a decoupled YaRN rotary part, a leading
dense stack, then expert layers with a shared expert and sigmoid,
bias-corrected, group-limited top-k routing (DeepSeek-V3 technical
report, arXiv:2412.19437, and the published modelling code).

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, NON-absorbed attention (keys and values are materialised per
head from the latent).  It imports nothing of ``apex_tpu``.  Weights
come one layer at a time in the published layout
(``cellbench/weights_mla_moe.py``: ``y = x @ W.T``), are upcast here,
and are dropped before the next layer is made: an expert layer's share
is 3.5 GB in float32.

Per layer, ``h`` the residual stream, all norms RMSNorm:

- ``x = norm(h)``; ``c_q = norm(x Wqa)``; ``q = c_q Wqb``, per head
  ``[q_nope, q_rope]``; ``[c_kv, k_r] = x Wkva``; ``c_kv = norm(c_kv)``;
  ``k_r`` and ``q_rope`` rotated (YaRN frequencies; one ``k_r`` for all
  heads); ``[k_nope, v] = c_kv Wkvb`` per head; ``k = [k_nope, k_r]``;
  causal softmax of ``q.k * scale``; ``h += concat(P v) Wo``;
- dense FFN: ``h += Wdown(silu(Wgate x') * Wup x')``;
- expert FFN: ``s = sigmoid(x' Wr)``; choice ``s + b``; each group is
  scored by the sum of its 2 best choices, the best ``topk_group``
  groups stay, the ``top_k`` best choices among them pick the experts,
  weighted by the original ``s`` over their sum (+1e-20) times
  ``routed_scaling_factor``; ``h += sum_e w_e E_e(x') + E_shared(x')``.

**The share.**  ``held`` (a range of expert ids) is the part of the
routed sum computed: assignments to other experts add nothing, though
the weights are normalised over all ``top_k``.  ``held=range(E)`` is
the uncut layer.

Departures from the published code, each on purpose: groups outside
the best ``topk_group`` are masked with ``-inf`` where the published
code writes 0.0 (the same choice unless a kept choice score is
negative); rotary pairs are rotated in place of the published
de-interleave-then-rotate-half, which is the same rotation in another
order of the 64 values, applied to queries and keys alike; the
multi-token-prediction module is not built (it does not enter the
logits).

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs are rounded, per tensor, to
``"float8_e4m3fn"`` (or to ``"bfloat16"``).
"""

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

EPS_DEFAULT = 1e-6


def _quantizer(quant: Optional[str]):
    if quant is None:
        return lambda x: x
    if quant == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "float8_e4m3fn":
        def rounded(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
            return (x / scale).astype(jnp.float8_e4m3fn) \
                .astype(jnp.float32) * scale
        return rounded
    raise ValueError(f"unknown control precision {quant!r}")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def yarn_inv_freq(conf: Dict):
    d = int(conf["qk_rope_head_dim"])
    base = float(conf["rope_theta"])
    extra = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    rs = conf.get("rope_scaling")
    if not rs:
        return extra
    factor, orig = float(rs["factor"]), \
        int(rs["original_max_position_embeddings"])

    def correction_dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / factor * (1.0 - mask) + extra * mask


def _mscale(factor, m):
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(conf: Dict) -> float:
    d = int(conf["qk_nope_head_dim"]) + int(conf["qk_rope_head_dim"])
    rs = conf.get("rope_scaling") or {}
    scale = d ** -0.5
    if rs.get("mscale_all_dim"):
        m = _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        scale *= m * m
    return scale


def rope(x, positions, conf: Dict):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by
    ``positions * inv_freq[i]``; laid out as the rotated first
    elements, then the rotated second elements.  ``x``: (S, ..., d)."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(conf)[None]
    rs = conf.get("rope_scaling") or {}
    m = 1.0
    if rs:
        m = _mscale(float(rs["factor"]), float(rs.get("mscale", 1))) \
            / _mscale(float(rs["factor"]),
                      float(rs.get("mscale_all_dim", 0) or 0))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), \
        (jnp.sin(ang) * m).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(x, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the attention's addition to the stream."""
    S = x.shape[0]
    heads = int(conf["num_attention_heads"])
    nope, dr = int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"])
    dv, rank = int(conf["v_head_dim"]), int(conf["kv_lora_rank"])
    eps = float(conf["rms_norm_eps"])
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    pos = jnp.arange(S)
    c_q = rms_norm(mm(x, w["self_attn.q_a_proj.weight"]),
                   w["self_attn.q_a_layernorm.weight"], eps)
    qh = mm(c_q, w["self_attn.q_b_proj.weight"]).reshape(S, heads,
                                                        nope + dr)
    q_nope, q_rope = qh[..., :nope], rope(qh[..., nope:], pos, conf)
    kv = mm(x, w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(kv[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_r = rope(kv[:, rank:], pos, conf)                       # (S, dr)
    kvb = mm(c_kv, w["self_attn.kv_b_proj.weight"]).reshape(S, heads,
                                                            nope + dv)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None], (S, heads, dr))],
        axis=-1)
    v = kvb[..., nope:]
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scores = jnp.einsum("shd,thd->hst", q(qf), q(k)) * softmax_scale(conf)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hst,thd->shd", q(probs), q(v)).reshape(S, heads * dv)
    return mm(o, w["self_attn.o_proj.weight"])


def gated_ffn(x, w_gate, w_up, w_down, q):
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(x, w_router, bias, conf: Dict):
    """(T, H) -> the ``top_k`` expert ids (T, k) and their weights
    (T, k), float32 throughout and never quantised (the router runs in
    float32 in the program too)."""
    T, E = x.shape[0], w_router.shape[0]
    k, G = int(conf["num_experts_per_tok"]), int(conf["n_group"])
    s = jax.nn.sigmoid(jnp.matmul(x, w_router.T))
    choice = s + bias[None]
    per_group = choice.reshape(T, G, E // G)
    group_score = jnp.sum(jnp.sort(per_group, axis=-1)[..., -2:], axis=-1)
    kept = jnp.argsort(-group_score, axis=-1, stable=True)[
        :, :int(conf["topk_group"])]
    group_ok = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None], axis=1)
    masked = jnp.where(jnp.repeat(group_ok, E // G, axis=1), choice,
                       -jnp.inf)
    ids = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(s, ids, axis=1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * float(conf["routed_scaling_factor"])
    return ids, weights


def routed_experts(x, w: Dict, conf: Dict, held: range, q):
    """The routed sum over the experts ``held``: every held expert runs
    on every token and is weighted by that token's weight for it (0
    where it was not chosen).  ``w``'s expert leaves hold ``held``'s
    experts in id order."""
    ids, weights = route(x, w["mlp.gate.weight"],
                         w["mlp.gate.e_score_correction_bias"], conf)

    def one(total, ew):
        e, wg, wu, wd = ew
        weight = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return total + weight[:, None] * gated_ffn(x, wg, wu, wd, q), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(held.start, held.stop),
         w["mlp.experts.gate_proj.weight"], w["mlp.experts.up_proj.weight"],
         w["mlp.experts.down_proj.weight"]))
    return total


def shared_expert(x, w: Dict, q):
    return gated_ffn(x, w["mlp.shared_experts.gate_proj.weight"],
                     w["mlp.shared_experts.up_proj.weight"],
                     w["mlp.shared_experts.down_proj.weight"], q)


def layer(h, w: Dict, conf: Dict, held: range, quant: Optional[str] = None):
    """One layer on the stream ``h`` (S, H); ``w`` in the published
    layout (any float dtype: upcast here).  A layer whose weights have
    ``mlp.gate.weight`` is an expert layer."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["rms_norm_eps"])
    h = h + attention(rms_norm(h, w["input_layernorm.weight"], eps), w,
                      conf, q)
    x = rms_norm(h, w["post_attention_layernorm.weight"], eps)
    if "mlp.gate.weight" in w:
        return h + routed_experts(x, w, conf, held, q) \
            + shared_expert(x, w, q)
    return h + gated_ffn(x, w["mlp.gate_proj.weight"],
                         w["mlp.up_proj.weight"], w["mlp.down_proj.weight"],
                         q)


def logits_at_each(conf: Dict, top: Dict, layer_weights, sequences,
                   positions, held: range, quant: Optional[str] = None,
                   layer_fn=None):
    """Full-forward logits of several sequences, each on its own (no
    batching): ``sequences[r]`` (S_r,) int32, ``positions[r]`` the
    positions wanted of it; returns a list of (len(positions[r]), V).
    ``top``: the embedding, final norm and head.  ``layer_weights(i)``
    makes layer ``i``'s weights when asked: the layers are the OUTER
    loop, so one layer's weights live at a time and each is made once.
    ``layer_fn``: a jitted :func:`layer` to reuse (``(h, w) -> h``)."""
    q = _quantizer(quant)
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w: layer(h, w, conf, held, quant))
        embed = top["model.embed_tokens.weight"].astype(jnp.float32)
        hs = [embed[t] for t in sequences]
        for i in range(int(conf["num_hidden_layers"])):
            w = layer_weights(i)
            hs = [fn(h, w) for h in hs]
            del w
        gain = top["model.norm.weight"].astype(jnp.float32)
        head = q(top["lm_head.weight"].astype(jnp.float32)).T
        return [jnp.matmul(q(rms_norm(h, gain, float(conf["rms_norm_eps"]))
                             [pos]), head)
                for h, pos in zip(hs, positions)]


def logits_at(conf: Dict, top: Dict, layer_weights, tokens, positions,
              held: range, quant: Optional[str] = None):
    """:func:`logits_at_each` for ONE sequence ``tokens`` (S,)."""
    return logits_at_each(conf, top, layer_weights, [tokens], [positions],
                          held, quant)[0]
