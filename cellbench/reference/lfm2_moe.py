"""The plain reference for an ``lfm2_moe`` decoder: layers that mix by a
gated short convolution or by grouped-query attention, over a dense or a
sparse feed-forward with a sigmoid router (LiquidAI's LFM2-8B-A1B model
card and ``transformers``' ``lfm2_moe`` modelling code for the
structure, as remembered).

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching, one sequence at a time.  The convolution is a sum of shifted
copies of the whole sequence; attention takes a dense causal softmax,
one head at a time; every HELD expert runs on every token and is
weighted by its routing weight or zero.  It imports nothing of
``apex_tpu``.  Weights come one layer at a time in the published layout
(``cellbench/weights_lfm2_moe.py``: ``y = x @ W.T``), are upcast here,
and are dropped before the next layer is made.  WHAT a layer is follows
from the leaves it is given: ``conv.in_proj.weight`` makes it a
convolution layer (else attention), ``feed_forward.gate.weight`` an
expert layer (else dense).

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
gain, ``norm_eps``::

    u = norm(h; operator_norm)
    conv:  [B | C | x] = u W_in          (hidden -> 3 x hidden, this order)
           z_t = B_t * x_t
           y_t = sum_{j < K} w[:, 0, j] z_{t - K + 1 + j}    K = conv_L_cache,
                 depthwise, causal, no bias, NO activation
           m = (C * y) W_out
    attn:  q, k, v = u Wq, u Wk, u Wv    heads of hidden / heads; query
                                         head i reads key/value head i // group
           q, k = norm(q; q_layernorm), norm(k; k_layernorm)   a head
           q, k rotated over the whole head (halves), theta rope_theta
           m = concat(softmax(q k^T / sqrt(head), causal) v) Wo
    h = h + m
    f = norm(h; ffn_norm)
    dense: h = h + (silu(f W1) * (f W3)) W2
    moe:   s = sigmoid(f Wr) over ALL experts
           chosen = the top_k largest of s + expert_bias (ties: the lowest id)
           g_e = s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor
           h = h + sum_{e chosen and held} g_e (silu(f W1_e) * (f W3_e)) W2_e

and ``logits = norm(h; embedding_norm) E^T`` with ``E`` the embedding
(tied).  A sequence's convolution STATE of a layer is its last ``K - 1``
rows of ``z`` (:func:`conv_tail`).

Departures from the published code, each on purpose: (1) the released
code keeps ``K`` columns of ``B * x`` a layer and rolls them; the last
``K - 1`` rows of ``z`` are the same function of the sequence; (2) the
experts NOT held here would add nothing (``held``; the benchmark's
configuration holds all of them); (3) an expert's three matrices come
stacked over the experts (``feed_forward.experts.w1.weight`` ``(E, F,
H)``), the published checkpoint keeps one module an expert.  What the
published ``config.json`` is silent on is in the configuration file's
``assumed``.

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs (the attention's two products too) are rounded,
per tensor, to ``"float8_e4m3fn"`` (or, on the bits, to ``"bfloat16"``).
The router stays float32 either way, as the configuration states it.
"""

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

# rounding ON THE BITS and the per-tensor float8 control
from cellbench.reference.evabyte import _quantizer, rope

NEG = -1e30
ROUTER_EPS = 1e-6


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def conv_inputs(u, w: Dict, q):
    """(S, H) normed input -> ``z = B * x`` and the gate ``C``, (S, H)
    each."""
    H = u.shape[1]
    bcx = jnp.matmul(q(u), q(w["conv.in_proj.weight"]).T)
    return bcx[:, :H] * bcx[:, 2 * H:], bcx[:, H:2 * H]


def short_conv(z, filt):
    """Causal depthwise convolution, no bias: ``z`` (S, C), ``filt`` (C,
    1, K) -> ``y[t, c] = sum_j filt[c, 0, j] z[t - K + 1 + j, c]``."""
    S, K = z.shape[0], filt.shape[-1]
    zp = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), z.dtype), z])
    return sum(filt[None, :, 0, j] * zp[j:j + S] for j in range(K))


def conv_mixer(u, w: Dict, q):
    """(S, H) normed input -> the convolution mixer's addition to the
    stream."""
    z, gate = conv_inputs(u, w, q)
    y = short_conv(z, w["conv.conv.weight"])
    return jnp.matmul(q(gate * y), q(w["conv.out_proj.weight"]).T)


def attention(u, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the attention mixer's addition to the
    stream, positions ``0 .. S - 1``."""
    S = u.shape[0]
    heads, kv = int(conf["num_attention_heads"]), \
        int(conf["num_key_value_heads"])
    d, eps = int(conf["hidden_size"]) // heads, float(conf["norm_eps"])
    theta = float(conf["rope_theta"])
    positions = jnp.arange(S)
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    qs = rms_norm(mm(u, w["self_attn.q_proj.weight"]).reshape(S, heads, d),
                  w["self_attn.q_layernorm.weight"], eps)
    ks = rms_norm(mm(u, w["self_attn.k_proj.weight"]).reshape(S, kv, d),
                  w["self_attn.k_layernorm.weight"], eps)
    qs, ks = rope(qs, positions, theta), rope(ks, positions, theta)
    vs = mm(u, w["self_attn.v_proj.weight"]).reshape(S, kv, d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    shared = jnp.arange(heads) // (heads // kv)

    def head(args):
        q_i, g = args
        s = jnp.matmul(q(q_i), q(ks[:, g]).T) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return jnp.matmul(q(p), q(vs[:, g]))

    o = jax.lax.map(head, (jnp.moveaxis(qs, 1, 0), shared))
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, heads * d),
              w["self_attn.out_proj.weight"])


def route(f, router_w, bias, top_k: int, scale: float = 1.0):
    """Sigmoid scores over ALL experts in float32, never quantised; the
    ``top_k`` largest of ``s + bias`` chosen (a stable sort: ties to the
    lowest id), their weights the ORIGINAL ``s`` over the chosen ones'
    sum plus 1e-6, times ``scale``.  Returns the (S, E) matrix of
    routing weights, zero where an expert is not chosen."""
    s = jax.nn.sigmoid(jnp.matmul(f, router_w.T))
    ids = jnp.argsort(-(s + bias[None]), axis=-1, stable=True)[:, :top_k]
    picked = jnp.take_along_axis(s, ids, axis=1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + ROUTER_EPS) * scale
    return jnp.zeros_like(s).at[jnp.arange(f.shape[0])[:, None], ids] \
        .set(weights)


def gated_ffn(f, w1, w3, w2, q):
    inner = jax.nn.silu(jnp.matmul(q(f), q(w1).T)) * jnp.matmul(q(f), q(w3).T)
    return jnp.matmul(q(inner), q(w2).T)


def experts(f, w: Dict, conf: Dict, held: range, q):
    """(S, H) normed input -> what the experts ``held`` add: every one
    of them on every token, weighted by its routing weight or zero.
    ``w``'s expert leaves hold ``held``'s experts in id order."""
    weights = route(f, w["feed_forward.gate.weight"],
                    w["feed_forward.expert_bias"],
                    int(conf["num_experts_per_tok"]),
                    float(conf["routed_scaling_factor"]))

    def one(total, ew):
        w1, w3, w2, g = ew
        return total + g[:, None] * gated_ffn(f, w1, w3, w2, q), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(f), (
        w["feed_forward.experts.w1.weight"],
        w["feed_forward.experts.w3.weight"],
        w["feed_forward.experts.w2.weight"],
        weights[:, held.start:held.stop].T))
    return total


def all_experts(conf: Dict) -> range:
    return range(int(conf["num_experts"]))


def layer(h, w: Dict, conf: Dict, quant: Optional[str] = None,
          held: Optional[range] = None, branches=("mixer", "ffn")):
    """One layer on the stream ``h`` (S, H), positions ``0 .. S - 1``;
    ``w`` in the published layout (any float dtype: upcast here).
    ``held``: the experts ``w`` holds (None: all the config counts).
    ``branches``: what is added (both: the model; one left out: what a
    test compares a program without it to)."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["norm_eps"])
    if "mixer" in branches:
        u = rms_norm(h, w["operator_norm.weight"], eps)
        h = h + (conv_mixer(u, w, q) if "conv.in_proj.weight" in w
                 else attention(u, w, conf, q))
    if "ffn" in branches:
        f = rms_norm(h, w["ffn_norm.weight"], eps)
        if "feed_forward.gate.weight" in w:
            h = h + experts(f, w, conf,
                            all_experts(conf) if held is None else held, q)
        else:
            h = h + gated_ffn(f, w["feed_forward.w1.weight"],
                              w["feed_forward.w3.weight"],
                              w["feed_forward.w2.weight"], q)
    return h


def embed(top: Dict, tokens):
    return top["model.embed_tokens.weight"].astype(jnp.float32)[tokens]


def hidden_after(conf: Dict, top: Dict, layer_weights: Callable, tokens,
                 layers: int, quant: Optional[str] = None, layer_fn=None):
    """The stream (S, H) of ONE sequence ``tokens`` (S,) after its first
    ``layers`` layers.  ``layer_weights(i)`` makes layer ``i``'s weights
    when asked: one layer's weights live at a time.  ``layer_fn``: a
    (jitted) :func:`layer` to reuse, ``(h, w) -> h``."""
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w: layer(h, w, conf, quant))
        h = embed(top, tokens)
        for i in range(layers):
            w = layer_weights(i)
            h = fn(h, w)
            del w
        return h


def logits_at(conf: Dict, top: Dict, layer_weights: Callable, tokens,
              positions, quant: Optional[str] = None, layer_fn=None):
    """Full-forward logits of ONE sequence ``tokens`` (S,) int32 at
    ``positions``: (len(positions), V).  The head is the embedding."""
    q = _quantizer(quant)
    h = hidden_after(conf, top, layer_weights, tokens,
                     int(conf["num_hidden_layers"]), quant, layer_fn)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, top["model.embedding_norm.weight"]
                     .astype(jnp.float32), float(conf["norm_eps"]))[positions]
        return jnp.matmul(
            q(x), q(top["model.embed_tokens.weight"].astype(jnp.float32)).T)


def conv_tail(conf: Dict, h, w: Dict, quant: Optional[str] = None,
              ends=None):
    """The convolution state that the layer with weights ``w`` (a
    convolution layer, published layout) holds once the sequence whose
    stream BEFORE that layer is ``h`` (S, H) has run through it: the
    last ``conv_L_cache - 1`` rows of ``z = B * x``, oldest first, (K -
    1, H); rows before the sequence's start are zero.  With ``ends``
    (lengths ``n <= S``): the state once the first ``n`` positions have,
    for each of them, (len(ends), K - 1, H)."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    K = int(conf["conv_L_cache"])
    with jax.default_matmul_precision("highest"):
        z, _ = conv_inputs(rms_norm(h, w["operator_norm.weight"],
                                    float(conf["norm_eps"])), w, q)
    z = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), z.dtype), z])
    if ends is None:
        return z[-(K - 1):]
    return jnp.stack([z[n:n + K - 1] for n in ends])
