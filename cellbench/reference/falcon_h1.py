"""The plain reference for a ``falcon_h1`` decoder: in every layer
grouped-query attention and a Mamba-2 state-space mixer (Dao and Gu,
"Transformers are SSMs", arXiv:2405.21060) side by side over one normed
input, then a gated MLP, with the muP multipliers of the published
config (Falcon-H1 report, arXiv:2507.22448; ``transformers``'
``falcon_h1`` modelling code for the structure).

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
chunking, no batching.  The state-space recurrence runs TOKEN BY TOKEN
under ``lax.scan``; attention takes a dense causal softmax, one head at
a time, so that a 2,560-token score matrix fits.  It imports nothing of
``apex_tpu``.  Weights come one layer at a time in the published layout
(``cellbench/weights_falcon_h1.py``: ``y = x @ W.T``), are upcast here,
and are dropped before the next layer is made.

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
gain::

    x0 = embed[tok] * embedding_multiplier
    x  = norm(h; input_layernorm)
    q, k, v = (x a_in) Wq, ((x a_in) Wk) key_multiplier, (x a_in) Wv
        heads of head_dim; query head i reads key/value head i // group
        q, k rotated over the whole head (halves), theta rope_theta
    A  = concat(softmax(q k^T / sqrt(head_dim), causal) v) Wo * a_out
    u  = ((x ssm_in) W_in) * mup_vector         [z | x' | B | C | dt]
        mup_vector: ssm_multipliers[0..4] on those five segments
    xBC = silu(conv([x' | B | C]) + conv_bias)  causal depthwise conv:
        y_t = sum_j w[c, j] in_{t - K + 1 + j}
    dt = softplus(dt + dt_bias);  Abar = exp(-exp(A_log[head]) dt)
    S  = Abar S + dt x' (outer) B[group of the head]      from zero
    y  = S . C[group] + D[head] x'
    y  = norm(y silu(z); a group of d_ssm / n_groups channels at a time)
    M  = (y W_out) * ssm_out
    h  = h + A + M
    x2 = norm(h; pre_ff_layernorm)
    h  = h + ((silu((x2 Wg) m_gate) * (x2 Wu)) Wd) * m_down

and ``logits = (norm(h; final_layernorm) W_head) * lm_head_multiplier``.
What the published ``config.json`` is silent on is in the configuration
file's ``assumed``.

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs (the attention's two products too) are rounded,
per tensor, to ``"float8_e4m3fn"`` (or, on the bits, to ``"bfloat16"``).
``state_dtype`` is a second control: the recurrent state rounded to that
dtype (on the bits) after every token, where float32 is stated.
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp

# rounding ON THE BITS (a cast there and back did not round on the chip:
# PERF.md, section 6, PR 30) and the per-tensor float8 control
from cellbench.reference.evabyte import _quantizer, _rounded

NEG = -1e30


def rms_norm(x, g, eps, groups: int = 1):
    """RMSNorm over each of ``groups`` equal parts of the last axis."""
    parts = x.reshape(x.shape[:-1] + (groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * g


def rope(x, theta: float):
    """``x`` (S, heads, d) rotated at positions ``0 .. S - 1``: the head
    splits in two halves and pair ``(x1[i], x2[i])`` turns by ``pos
    theta^(-2i/d)``."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def attention(x, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the attention branch's addition to the
    stream (its output multiplier applied)."""
    S = x.shape[0]
    heads, kv = int(conf["num_attention_heads"]), \
        int(conf["num_key_value_heads"])
    d = int(conf["head_dim"])
    theta = float(conf["rope_theta"])
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    xin = x * float(conf["attention_in_multiplier"])
    qs = rope(mm(xin, w["self_attn.q_proj.weight"]).reshape(S, heads, d),
              theta)
    ks = rope((mm(xin, w["self_attn.k_proj.weight"])
               * float(conf["key_multiplier"])).reshape(S, kv, d), theta)
    vs = mm(xin, w["self_attn.v_proj.weight"]).reshape(S, kv, d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    shared = jnp.arange(heads) // (heads // kv)

    def head(args):
        q_i, g = args
        s = jnp.matmul(q(q_i), q(ks[:, g]).T) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        return jnp.matmul(q(p), q(vs[:, g]))

    o = jax.lax.map(head, (jnp.moveaxis(qs, 1, 0), shared))
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, heads * d),
              w["self_attn.o_proj.weight"]) \
        * float(conf["attention_out_multiplier"])


def mup_vector(conf: Dict):
    """``ssm_multipliers`` over ``in_proj``'s five segments (z, x, B, C,
    dt), one value an output."""
    d_ssm = int(conf["mamba_d_ssm"])
    gn = int(conf["mamba_n_groups"]) * int(conf["mamba_d_state"])
    widths = (d_ssm, d_ssm, gn, gn, int(conf["mamba_n_heads"]))
    return jnp.concatenate([jnp.full((n,), float(m), jnp.float32)
                            for n, m in zip(widths, conf["ssm_multipliers"])])


def short_conv(x, w, b):
    """Causal depthwise convolution with a bias: ``x`` (S, C), ``w`` (C,
    1, K), ``b`` (C,) -> ``y[t, c] = b[c] + sum_j w[c, 0, j] x[t - K + 1
    + j, c]``."""
    S, K = x.shape[0], w.shape[-1]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return b[None] + sum(w[None, :, 0, j] * xp[j:j + S] for j in range(K))


def state_space(x, dt, A, B, C, D, state_dtype=None):
    """The Mamba-2 recurrence from a zero state, one token a scan step.
    ``x``: (S, heads, P); ``dt``: (S, heads) after its softplus; ``A``
    (heads,) negative; ``B``, ``C``: (S, groups, N); ``D``: (heads,).
    Returns the outputs (S, heads, P) and the state after the last token
    (heads, P, N)."""
    heads, P = x.shape[1], x.shape[2]
    groups, N = B.shape[1], B.shape[2]
    of = jnp.arange(heads) // (heads // groups)      # a head's group

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = S * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[of][:, None, :]
        if state_dtype is not None:
            S = _rounded(S, state_dtype)
        return S, jnp.einsum("hpn,hn->hp", S, C_t[of]) + D[:, None] * x_t

    S, y = jax.lax.scan(step, jnp.zeros((heads, P, N), jnp.float32),
                        (x, dt, B, C))
    return y, S


def _ssm_inputs(x, w: Dict, conf: Dict, q):
    """(S, H) normed input -> the gate ``z`` (S, d_ssm) and the
    recurrence's ``x, dt, A, B, C, D``."""
    S = x.shape[0]
    d_ssm, heads = int(conf["mamba_d_ssm"]), int(conf["mamba_n_heads"])
    G, N = int(conf["mamba_n_groups"]), int(conf["mamba_d_state"])
    u = jnp.matmul(q(x * float(conf["ssm_in_multiplier"])),
                   q(w["mamba.in_proj.weight"]).T) * mup_vector(conf)
    z, xbc, dt = u[:, :d_ssm], u[:, d_ssm:2 * d_ssm + 2 * G * N], \
        u[:, 2 * d_ssm + 2 * G * N:]
    xbc = jax.nn.silu(short_conv(xbc, w["mamba.conv1d.weight"],
                                 w["mamba.conv1d.bias"]))
    return z, (xbc[:, :d_ssm].reshape(S, heads, d_ssm // heads),
               jax.nn.softplus(dt + w["mamba.dt_bias"]),
               -jnp.exp(w["mamba.A_log"]),
               xbc[:, d_ssm:d_ssm + G * N].reshape(S, G, N),
               xbc[:, d_ssm + G * N:].reshape(S, G, N), w["mamba.D"])


def mamba(x, w: Dict, conf: Dict, q, state_dtype=None):
    """(S, H) normed input -> the state-space branch's addition to the
    stream (its output multiplier applied)."""
    z, inputs = _ssm_inputs(x, w, conf, q)
    y, _ = state_space(*inputs, state_dtype)
    y = rms_norm(y.reshape(z.shape) * jax.nn.silu(z), w["mamba.norm.weight"],
                 float(conf["rms_norm_eps"]), int(conf["mamba_n_groups"]))
    return jnp.matmul(q(y), q(w["mamba.out_proj.weight"]).T) \
        * float(conf["ssm_out_multiplier"])


def first_ssm_state(conf: Dict, top: Dict, w: Dict, tokens,
                    quant: Optional[str] = None, state_dtype=None):
    """The recurrent state (heads, P, N) that layer 0's state-space
    mixer holds once it has taken ``tokens`` (S,) in: it depends on the
    token ids and that layer's own projections alone.  ``w``: layer 0's
    weights in the published layout."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        h = top["model.embed_tokens.weight"].astype(jnp.float32)[tokens] \
            * float(conf["embedding_multiplier"])
        x = rms_norm(h, w["input_layernorm.weight"],
                     float(conf["rms_norm_eps"]))
        return state_space(*_ssm_inputs(x, w, conf, q)[1], state_dtype)[1]


def gated_mlp(x, w: Dict, conf: Dict, q):
    gate, down = (float(m) for m in conf["mlp_multipliers"])
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    inner = jax.nn.silu(mm(x, w["feed_forward.gate_proj.weight"]) * gate) \
        * mm(x, w["feed_forward.up_proj.weight"])
    return mm(inner, w["feed_forward.down_proj.weight"]) * down


def layer(h, w: Dict, conf: Dict, quant: Optional[str] = None,
          state_dtype=None, branches=("attention", "mamba")):
    """One layer on the stream ``h`` (S, H), positions ``0 .. S - 1``;
    ``w`` in the published layout (any float dtype: upcast here).
    ``branches``: the mixers that are added (both: the model; one left
    out: what a test compares a program without it to)."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["rms_norm_eps"])
    x = rms_norm(h, w["input_layernorm.weight"], eps)
    mixed = h
    if "attention" in branches:
        mixed = mixed + attention(x, w, conf, q)
    if "mamba" in branches:
        mixed = mixed + mamba(x, w, conf, q, state_dtype)
    return mixed + gated_mlp(
        rms_norm(mixed, w["pre_ff_layernorm.weight"], eps), w, conf, q)


def hidden_after(conf: Dict, top: Dict, layer_weights, tokens, layers: int,
                 quant: Optional[str] = None, layer_fn=None,
                 state_dtype=None):
    """The stream (S, H) of ONE sequence ``tokens`` (S,) after its first
    ``layers`` layers.  ``layer_weights(i)`` makes layer ``i``'s weights
    when asked: one layer's weights live at a time."""
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w: layer(h, w, conf, quant, state_dtype))
        h = top["model.embed_tokens.weight"].astype(jnp.float32)[tokens] \
            * float(conf["embedding_multiplier"])
        for i in range(layers):
            w = layer_weights(i)
            h = fn(h, w)
            del w
        return h


def logits_at(conf: Dict, top: Dict, layer_weights, tokens, positions,
              quant: Optional[str] = None, layer_fn=None, state_dtype=None):
    """Full-forward logits of ONE sequence ``tokens`` (S,) int32 at
    ``positions``: (len(positions), V), V the rows of the head given.
    ``layer_fn``: a jitted :func:`layer` to reuse, ``(h, w) -> h``."""
    q = _quantizer(quant)
    h = hidden_after(conf, top, layer_weights, tokens,
                     int(conf["num_hidden_layers"]), quant, layer_fn,
                     state_dtype)
    with jax.default_matmul_precision("highest"):
        gain = top["model.final_layernorm.weight"].astype(jnp.float32)
        head = q(top["lm_head.weight"].astype(jnp.float32)).T
        x = rms_norm(h, gain, float(conf["rms_norm_eps"]))[positions]
        return jnp.matmul(q(x), head) * float(conf["lm_head_multiplier"])
