"""The plain reference for an ``evabyte`` decoder: EVA attention (Zheng,
Yuan, Wang, Kong, "Efficient Attention via Control Variates",
arXiv:2302.04542) in the deterministic, query-independent form of
EvaByte's released modelling code, a gated-SiLU MLP, a head of
``num_pred_heads`` x ``vocab_size`` rows.

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching; attention window by window and a block of heads at a time, so
that 18k positions fit.  It imports nothing of ``apex_tpu``.  Weights
come one layer at a time in the published layout
(``cellbench/weights_evabyte.py``: ``y = x @ W.T``), are upcast here,
and are dropped before the next layer is made.

Per layer, ``h`` the float32 residual stream (``fp32_skip_add``)::

    x = h / rms(h) * (1 + g)                       (norm_add_unit_offset)
    q, k, v = x Wq, x Wk, x Wv                     heads of d, no bias
    q_t, k_t rotated at absolute position t        (whole head, theta)
    chunk c = positions [chunk c, chunk (c + 1)):
      alpha_j = softmax_j(phi . k_j)   ktilde_c = sum alpha_j k_j + mu
                                       vtilde_c = sum alpha_j v_j
    query t of window w = t // window:
      a_s = q_t . k_s / sqrt(d)        s in window w, s <= t
      b_c = q_t . ktilde_c / sqrt(d)   c a chunk of windows 0 .. w - 1
      p = softmax([a ; b]);  o_t = sum p_s v_s + sum p_c vtilde_c
    h += concat(o) Wo;   h += Wd (silu(Wg x') * Wu x'),  x' = norm(h)

and ``logits = norm(h) W_head`` after the last layer, all
``num_pred_heads`` heads (head ``i``, rows ``[i V, (i + 1) V)``, predicts
byte ``t + 1 + i``).  What the published ``config.json`` is silent on
is written in the configuration file's ``assumed``: no scale on ``phi .
k`` beyond the one in ``phi``'s initialisation; ``mu`` is added to the
pooled key only; the pooled pairs are made of POST-rotary keys; a
chunk's pair is visible to a query only once its whole window has
closed; a sequence's last, partial chunk has none.

:func:`pooled_pairs` is the pooling alone, over keys and values given
to it: what a cache's pooled columns are held against, given the own
columns it made them of.

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs (the pooling's and the attention's products
too) are rounded, per tensor, to ``"float8_e4m3fn"`` (or, on the bits,
to ``"bfloat16"``).  ``alpha_dtype`` is a second control: the pooling
weights ``alpha`` rounded to that dtype (on the bits) before the two
weighted sums, where float32 is stated.
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp

#: heads a block of the window-by-window attention holds: 8 x 2,048 x
#: (2,048 + 1,024) float32 scores are 200 MB
HEAD_BLOCK = 8
NEG = -1e30


def _rounded(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s mantissa (to nearest,
    ties to even) on the BITS, for a ``dtype`` with float32's exponent
    (bfloat16).  Not a cast there and back: on the chip a control built
    on that pair of converts came out bit for bit the float32 reference
    (PERF.md, section 6, PR 30); integer operations on the bits cannot
    be dropped."""
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
    """RMSNorm whose gain is ``1 + g``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def rope(x, positions, theta: float):
    """``x`` (S, heads, d) rotated at ``positions`` (S,): the head
    splits in two halves and pair ``(x1[i], x2[i])`` turns by ``pos
    theta^(-2i/d)``."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def projections(x, w: Dict, conf: Dict, q):
    """Normed rows (S, H) -> rotated queries and keys, values, (S,
    heads, d) each, positions ``0 .. S - 1``."""
    heads = int(conf["num_attention_heads"])
    S = x.shape[0]
    xq = q(x)
    out = [jnp.matmul(xq, q(w[f"self_attn.{n}_proj.weight"]).T)
           .reshape(S, heads, -1) for n in "qkv"]
    pos = jnp.arange(S)
    theta = float(conf["rope_theta"])
    return rope(out[0], pos, theta), rope(out[1], pos, theta), out[2]


def summaries(k, v, w: Dict, conf: Dict, q, alpha_dtype=None):
    """``(ktilde, vtilde)``, (S // chunk, heads, d) each: every WHOLE
    chunk of ``k``, ``v`` (S, heads, d) pooled (module doc)."""
    chunk = int(conf["chunk_size"])
    S, heads, d = k.shape
    n = S // chunk
    phi = w["self_attn.adaptive_phi"].reshape(heads, d)
    mu = w["self_attn.adaptive_mu_k"].reshape(heads, d)
    kc = k[:n * chunk].reshape(n, chunk, heads, d)
    vc = v[:n * chunk].reshape(n, chunk, heads, d)
    alpha = jax.nn.softmax(jnp.einsum("cjhd,hd->chj", q(kc), q(phi)),
                           axis=-1)
    if alpha_dtype is not None:
        alpha = _rounded(alpha, alpha_dtype)
    return (jnp.einsum("chj,cjhd->chd", q(alpha), q(kc)) + mu,
            jnp.einsum("chj,cjhd->chd", q(alpha), q(vc)))


def attention(x, w: Dict, conf: Dict, q, alpha_dtype=None):
    """The EVA layer on normed rows ``x`` (S, H) -> (S, H)."""
    window, chunk = int(conf["window_size"]), int(conf["chunk_size"])
    per = window // chunk
    qs, k, v = projections(x, w, conf, q)
    S, heads, d = k.shape
    kt, vt = summaries(k, v, w, conf, q, alpha_dtype)
    scale = d ** -0.5
    rows = []
    for start in range(0, S, window):
        stop = min(start + window, S)
        seen = start // window * per
        t = jnp.arange(start, stop)
        causal = t[:, None] >= t[None, :]
        heads_out = []
        for h0 in range(0, heads, HEAD_BLOCK):
            hb = slice(h0, h0 + HEAD_BLOCK)
            qw = q(qs[start:stop, hb])
            a = jnp.einsum("qhd,khd->hqk", qw, q(k[start:stop, hb])) * scale
            a = jnp.where(causal[None], a, NEG)
            vals = v[start:stop, hb]
            if seen:        # the first window sees no pooled pair
                b = jnp.einsum("qhd,khd->hqk", qw, q(kt[:seen, hb])) * scale
                a = jnp.concatenate([a, b], axis=-1)
                vals = jnp.concatenate([vals, vt[:seen, hb]], axis=0)
            p = jax.nn.softmax(a, axis=-1)
            heads_out.append(jnp.einsum("hqk,khd->qhd", q(p), q(vals)))
        rows.append(jnp.concatenate(heads_out, axis=1))
    o = jnp.concatenate(rows, axis=0).reshape(S, heads * d)
    return jnp.matmul(q(o), q(w["self_attn.o_proj.weight"]).T)


def gated_ffn(x, w: Dict, q):
    xq = q(x)
    inner = jax.nn.silu(jnp.matmul(xq, q(w["mlp.gate_proj.weight"]).T)) \
        * jnp.matmul(xq, q(w["mlp.up_proj.weight"]).T)
    return jnp.matmul(q(inner), q(w["mlp.down_proj.weight"]).T)


def layer(h, w: Dict, conf: Dict, quant: Optional[str] = None,
          alpha_dtype=None):
    """One layer on the stream ``h`` (S, H), positions ``0 .. S - 1``;
    ``w`` in the published layout (any float dtype: upcast here)."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["rms_norm_eps"])
    h = h + attention(rms_norm(h, w["input_layernorm.weight"], eps), w,
                      conf, q, alpha_dtype)
    return h + gated_ffn(
        rms_norm(h, w["post_attention_layernorm.weight"], eps), w, q)


def pooled_pairs(conf: Dict, w: Dict, k, v, quant: Optional[str] = None,
                 alpha_dtype=None, cache_dtype=None):
    """The pooling alone, as the cache states it: ``k``, ``v`` (S,
    heads, d), the keys (post-rotary) and values of whole chunks AS
    CACHED (given in any float dtype), pooled in float32 (module doc)
    and rounded on the bits to ``cache_dtype`` (bfloat16; None: a
    float32 cache, no rounding).  ``w``: the layer's weights.  Returns
    ``(ktilde, vtilde)``, (S // chunk, heads, d) each, float32."""
    q = _quantizer(quant)
    with jax.default_matmul_precision("highest"):
        w = {n: x.astype(jnp.float32) for n, x in w.items()
             if "adaptive" in n}
        kt, vt = summaries(k.astype(jnp.float32), v.astype(jnp.float32), w,
                           conf, q, alpha_dtype)
        if cache_dtype is None:
            return kt, vt
        return _rounded(kt, cache_dtype), _rounded(vt, cache_dtype)


def logits_at(conf: Dict, top: Dict, layer_weights, tokens, positions,
              quant: Optional[str] = None, layer_fn=None, alpha_dtype=None):
    """Full-forward logits of ONE sequence ``tokens`` (S,) int32 at
    ``positions``: (len(positions), num_pred_heads * vocab_size), every
    head.  ``layer_weights(i)`` makes layer ``i``'s weights when asked:
    one layer's weights live at a time.  ``layer_fn``: a jitted
    :func:`layer` to reuse, ``(h, w) -> h``."""
    q = _quantizer(quant)
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w: layer(h, w, conf, quant, alpha_dtype))
        h = top["model.embed_tokens.weight"].astype(jnp.float32)[tokens]
        for i in range(int(conf["num_hidden_layers"])):
            w = layer_weights(i)
            h = fn(h, w)
            del w
        gain = top["model.norm.weight"].astype(jnp.float32)
        head = q(top["lm_head.weight"].astype(jnp.float32)).T
        x = rms_norm(h, gain, float(conf["rms_norm_eps"]))[positions]
        return jnp.matmul(q(x), head)
