"""The plain reference for a ``kimi_linear``-style decoder: Kimi Delta
Attention (KDA) and positionless multi-head latent attention (MLA)
mixers by a per-layer pattern, one leading dense layer, then expert
layers with a shared expert and sigmoid, bias-corrected top-k routing
(Kimi Linear report, arXiv:2510.26692; the KDA layer of
``flash-linear-attention`` that it points to).

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
chunking, no batching.  KDA is its recurrence TOKEN BY TOKEN under
``lax.scan``; MLA materialises keys and values per head and takes a
dense softmax (one head at a time, so that a 5,000-token score matrix
fits).  It imports nothing of ``apex_tpu``.  Weights come one layer at a
time in the published layout (``cellbench/weights_kda_mla_moe.py``:
``y = x @ W.T``), are upcast here, and are dropped before the next layer
is made.

Per layer, ``h`` the residual stream, ``x = norm(h)``, all norms
RMSNorm:

- KDA (``linear_attn_config.kda_layers``, 1-based): ``q, k, v =
  silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))``, ``conv`` a
  causal depthwise convolution over the last ``short_conv_kernel_size``
  positions (``y_t = sum_j w[c, j] x_{t-K+1+j}``); per head of size
  ``d``: ``q`` and ``k`` L2-normalised (``x / sqrt(sum x^2 + 1e-6)``),
  ``q`` scaled by ``d^-1/2``; ``g = -exp(A_log[head]) softplus((x Wfa)
  Wfb + dt_bias)`` a head and key channel; ``beta = sigmoid(x Wb)`` a
  head; state ``S`` (d x d) a head from zero: ``S' = Diag(exp(g)) S``,
  ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``; ``o =
  norm_head(o) * sigmoid((x Wga) Wgb)``; ``h += o Wo``;
- MLA: ``q = x Wq`` per head ``[q_nope, q_pe]``; ``[c_kv, k_pe] = x
  Wkva``; ``c_kv = norm(c_kv)``; ``[k_nope, v] = c_kv Wkvb`` per head;
  ``k = [k_nope, k_pe]`` (one ``k_pe`` for all heads, NOT rotated:
  ``mla_use_nope``); causal softmax of ``q.k (nope + rope)^-1/2``;
  ``h += concat(P v) Wo``;
- dense FFN: ``h += Wdown(silu(Wgate x') * Wup x')``;
- expert FFN: ``s = sigmoid(x' Wr)``; the ``num_experts_per_token``
  best ``s + b`` pick the experts (``num_expert_group`` 1: no group
  limit), weighted by the original ``s`` over their sum (+1e-20) times
  ``routed_scaling_factor``; ``h += sum_e w_e E_e(x') + E_shared(x')``.

**The share.**  ``held`` (a range of expert ids) is the part of the
routed sum computed, as in ``reference/mla_moe.py``.

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs are rounded, per tensor, to ``"float8_e4m3fn"``
(or to ``"bfloat16"``).  ``state_dtype`` rounds the KDA state to that
dtype after every token (a second control: a bfloat16 state where
float32 is stated).
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def _rounded(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s mantissa (to nearest,
    ties to even) on the BITS, for a ``dtype`` with float32's exponent
    (bfloat16).  Not a cast there and back: on the chip a control built
    on that pair of converts came out bit for bit the float32 reference
    (it read 0 in every number, twice: PERF.md, section 6, PR 30; a
    compiler may drop such a pair as excess precision).  Integer
    operations on the bits cannot be dropped."""
    info = jnp.finfo(dtype)
    if info.nexp != 8:
        raise ValueError(f"{dtype}: not float32's exponent")
    drop = 23 - info.nmant
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & 1)
    u = u & jnp.uint32((0xFFFFFFFF << drop) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _quantizer(quant: Optional[str]):
    if quant is None:
        return lambda x: x
    if quant == "bfloat16":
        return lambda x: _rounded(x, jnp.bfloat16)
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


def short_conv(x, w):
    """Causal depthwise convolution: ``x`` (S, C), ``w`` (C, 1, K) ->
    ``y[t, c] = sum_j w[c, 0, j] x[t - K + 1 + j, c]``."""
    S, K = x.shape[0], w.shape[-1]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(w[None, :, 0, j] * xp[j:j + S] for j in range(K))


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The KDA recurrence from a zero state, one token a scan step.
    ``q``, ``k``, ``g``: (S, heads, d); ``v``: (S, heads, d); ``beta``:
    (S, heads).  Returns the outputs (S, heads, d) and the state after
    the last token (heads, d, d)."""
    heads, d = q.shape[1], q.shape[2]

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        if state_dtype is not None:
            S = _rounded(S, state_dtype)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, g, beta))
    return o, S


def _kda_inputs(x, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the recurrence's ``q, k, v, g, beta``."""
    lin = conf["linear_attn_config"]
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    S = x.shape[0]
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)

    def branch(n):
        y = short_conv(mm(x, w[f"self_attn.{n}_proj.weight"]),
                       w[f"self_attn.{n}_conv1d.weight"])
        return jax.nn.silu(y).reshape(S, heads, d)

    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
    low = mm(mm(x, w["self_attn.f_a_proj.weight"]),
             w["self_attn.f_b_proj.weight"])
    g = -jnp.exp(w["self_attn.A_log"])[None, :, None] * jax.nn.softplus(
        low + w["self_attn.dt_bias"]).reshape(S, heads, d)
    beta = jax.nn.sigmoid(mm(x, w["self_attn.b_proj.weight"]))
    return (unit(branch("q")) * d ** -0.5, unit(branch("k")), branch("v"),
            g, beta)


def kda(x, w: Dict, conf: Dict, q, state_dtype=None):
    """(S, H) normed input -> the KDA mixer's addition to the stream."""
    lin = conf["linear_attn_config"]
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    S = x.shape[0]
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    o, _ = delta_rule(*_kda_inputs(x, w, conf, q), state_dtype)
    gate = mm(mm(x, w["self_attn.g_a_proj.weight"]),
              w["self_attn.g_b_proj.weight"]).reshape(S, heads, d)
    o = rms_norm(o, w["self_attn.o_norm.weight"],
                 float(conf["rms_norm_eps"])) * jax.nn.sigmoid(gate)
    return mm(o.reshape(S, heads * d), w["self_attn.o_proj.weight"])


def first_kda_state(conf: Dict, top: Dict, w: Dict, tokens,
                    quant: Optional[str] = None, state_dtype=None):
    """The recurrent state (heads, d, d) that layer 0's KDA mixer holds
    once it has taken ``tokens`` (S,) in: the one state of the model
    that depends on nothing but the token ids and that layer's own
    projections (no router has touched its input).  ``w``: layer 0's
    weights in the published layout."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        h = top["model.embed_tokens.weight"].astype(jnp.float32)[tokens]
        x = rms_norm(h, w["input_layernorm.weight"],
                     float(conf["rms_norm_eps"]))
        return delta_rule(*_kda_inputs(x, w, conf, q), state_dtype)[1]


def mla(x, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the MLA mixer's addition to the stream."""
    if not conf.get("mla_use_nope") or conf.get("q_lora_rank") is not None:
        raise NotImplementedError(
            "this reference is the positionless, one-query-matrix MLA of "
            "kimi_linear (mla_use_nope, q_lora_rank: null)")
    S = x.shape[0]
    heads = int(conf["num_attention_heads"])
    nope, dr = int(conf["qk_nope_head_dim"]), int(conf["qk_rope_head_dim"])
    dv, rank = int(conf["v_head_dim"]), int(conf["kv_lora_rank"])
    eps = float(conf["rms_norm_eps"])
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    qh = mm(x, w["self_attn.q_proj.weight"]).reshape(S, heads, nope + dr)
    kv = mm(x, w["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(kv[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    kvb = mm(c_kv, w["self_attn.kv_b_proj.weight"]).reshape(S, heads,
                                                            nope + dv)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(kv[:, None, rank:], (S, heads, dr))], axis=-1)
    v = kvb[..., nope:]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):
        q_i, k_i, v_i = qkv
        s = jnp.matmul(q(q_i), q(k_i).T) * (nope + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(q(p), q(v_i))

    o = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (qh, k, v)))
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, heads * dv),
              w["self_attn.o_proj.weight"])


def gated_ffn(x, w_gate, w_up, w_down, q):
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def route(x, w_router, bias, conf: Dict):
    """(T, H) -> the chosen expert ids (T, k) and their weights (T, k),
    float32 throughout and never quantised (the router runs in float32
    in the program too)."""
    if int(conf.get("num_expert_group", 1)) != 1:
        raise NotImplementedError("a group limit: reference/mla_moe.py")
    k = int(conf["num_experts_per_token"])
    s = jax.nn.sigmoid(jnp.matmul(x, w_router.T))
    ids = jnp.argsort(-(s + bias[None]), axis=-1, stable=True)[:, :k]
    picked = jnp.take_along_axis(s, ids, axis=1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
        * float(conf["routed_scaling_factor"])
    return ids, weights


def routed_experts(x, w: Dict, conf: Dict, held: range, q):
    """The routed sum over the experts ``held``: every held expert runs
    on every token and is weighted by that token's weight for it (0
    where it was not chosen)."""
    moe = "block_sparse_moe."
    ids, weights = route(x, w[moe + "gate.weight"],
                         w[moe + "gate.e_score_correction_bias"], conf)

    def one(total, ew):
        e, wg, wu, wd = ew
        weight = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return total + weight[:, None] * gated_ffn(x, wg, wu, wd, q), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(held.start, held.stop), w[moe + "experts.w1.weight"],
         w[moe + "experts.w3.weight"], w[moe + "experts.w2.weight"]))
    return total


def shared_expert(x, w: Dict, q):
    moe = "block_sparse_moe.shared_experts."
    return gated_ffn(x, w[moe + "gate_proj.weight"],
                     w[moe + "up_proj.weight"], w[moe + "down_proj.weight"],
                     q)


def layer(h, w: Dict, conf: Dict, held: range, quant: Optional[str] = None,
          state_dtype=None):
    """One layer on the stream ``h`` (S, H); ``w`` in the published
    layout (any float dtype: upcast here).  A layer whose weights have
    ``self_attn.A_log`` mixes by KDA; one with
    ``block_sparse_moe.gate.weight`` is an expert layer."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["rms_norm_eps"])
    x = rms_norm(h, w["input_layernorm.weight"], eps)
    h = h + (kda(x, w, conf, q, state_dtype) if "self_attn.A_log" in w
             else mla(x, w, conf, q))
    x = rms_norm(h, w["post_attention_layernorm.weight"], eps)
    if "block_sparse_moe.gate.weight" in w:
        return h + routed_experts(x, w, conf, held, q) \
            + shared_expert(x, w, q)
    return h + gated_ffn(x, w["mlp.gate_proj.weight"],
                         w["mlp.up_proj.weight"], w["mlp.down_proj.weight"],
                         q)


def logits_at_each(conf: Dict, top: Dict, layer_weights, sequences,
                   positions, held: range, quant: Optional[str] = None,
                   layer_fn=None, state_dtype=None):
    """Full-forward logits of several sequences, each on its own (no
    batching): ``sequences[r]`` (S_r,) int32, ``positions[r]`` the
    positions wanted of it; returns a list of (len(positions[r]), V).
    ``layer_weights(i)`` makes layer ``i``'s weights when asked: the
    layers are the OUTER loop, so one layer's weights live at a time.
    ``layer_fn``: jitted :func:`layer`\\ s to reuse, ``(h, w) -> h``
    (one compile a kind of layer and a length)."""
    q = _quantizer(quant)
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w: layer(h, w, conf, held, quant,
                                             state_dtype))
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
              held: range, quant: Optional[str] = None, state_dtype=None):
    """:func:`logits_at_each` for ONE sequence ``tokens`` (S,)."""
    return logits_at_each(conf, top, layer_weights, [tokens], [positions],
                          held, quant, state_dtype=state_dtype)[0]
