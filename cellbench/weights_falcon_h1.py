"""Seeded weights for a ``falcon_h1`` model (attention and a Mamba-2
mixer in parallel in every layer, a gated MLP), in the PUBLISHED layout
and ONE LAYER at a time.

As ``cellbench/weights_mla_moe.py`` (whose key derivation this module
shares): ``layer_weights(conf, key, index)`` and ``top_weights(conf,
key)`` are pure functions of the configuration file, a PRNG key and the
layer's 0-based index; a ``weight`` is ``(out, in)``: ``y = x @ W.T``.
Every leaf is ROUNDED TO BFLOAT16 after its draw, so that a bf16 program
and a float32 reference hold the same numbers, but ``A_log``,
``dt_bias`` and ``D``, which stay float32 as the published checkpoints
keep them.

**Scales.**  The published muP multipliers shrink both mixers' outputs
(0.0375, 0.088), the MLP's (0.011), the keys (0.011) and the logits
(1 / 128); trained weights are as much larger.  A seeded matrix at
N(0, 0.02) would leave every branch a thousandth of the stream and the
comparison blind to it.  So every matrix is drawn ``N(0, 1 / fan_in)``
OVER the multipliers on its path (the input's and its own output's: per
segment for ``in_proj``): unit variance in, unit variance out, after
the multipliers.  The embedding is ``N(0, 1)`` over
``embedding_multiplier`` and the head ``N(0, 1 / hidden)`` over
``lm_head_multiplier`` (logits of unit variance).  Gains are ``1 + N(0,
0.02)``; the convolution's filter ``N(0, 1 / taps)``, its bias ``N(0,
0.1)``.  ``A = exp(A_log)`` is uniform in [1, 16] a head, ``dt =
softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``D`` is 1: Mamba-2's
reference initialisation, a head's memory ``1 / (A dt)`` spans one to a
thousand tokens.
"""

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.weights_mla_moe import layer_key, seed_key  # noqa: F401

GAIN_STD = 0.02
CONV_BIAS_STD = 0.1
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)
_TOP = ("model.embed_tokens.weight", "model.final_layernorm.weight",
        "lm_head.weight")

#: every leaf of a layer, in key order (a leaf's key is folded from its
#: place here: adding one moves no other)
_ORDER = (
    "input_layernorm.weight", "pre_ff_layernorm.weight",
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "mamba.in_proj.weight", "mamba.conv1d.weight", "mamba.conv1d.bias",
    "mamba.A_log", "mamba.dt_bias", "mamba.D", "mamba.norm.weight",
    "mamba.out_proj.weight",
    "feed_forward.gate_proj.weight", "feed_forward.up_proj.weight",
    "feed_forward.down_proj.weight",
)


def sizes(conf: Dict) -> Dict[str, int]:
    G, N = int(conf["mamba_n_groups"]), int(conf["mamba_d_state"])
    d_ssm = int(conf["mamba_d_ssm"])
    return {
        "V": int(conf["vocab_size"]), "H": int(conf["hidden_size"]),
        "L": int(conf["num_hidden_layers"]),
        "heads": int(conf["num_attention_heads"]),
        "kv_heads": int(conf["num_key_value_heads"]),
        "d": int(conf["head_dim"]), "F": int(conf["intermediate_size"]),
        "d_ssm": d_ssm, "ssm_heads": int(conf["mamba_n_heads"]),
        "P": int(conf["mamba_d_head"]), "N": N, "G": G,
        "conv": int(conf["mamba_d_conv"]), "conv_dim": d_ssm + 2 * G * N,
        "in": 2 * d_ssm + 2 * G * N + int(conf["mamba_n_heads"]),
    }


def segments(conf: Dict) -> Tuple[Tuple[str, int], ...]:
    """The five segments of ``in_proj``'s outputs, in order, with their
    widths: the order of ``ssm_multipliers``."""
    s = sizes(conf)
    return (("z", s["d_ssm"]), ("x", s["d_ssm"]), ("B", s["G"] * s["N"]),
            ("C", s["G"] * s["N"]), ("dt", s["ssm_heads"]))


def layer_leaves(conf: Dict) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of a layer's leaves (every layer is alike).
    Kinds: matrix, gain, conv, conv_bias, a_log, dt_bias, ones."""
    s = sizes(conf)
    H, d = s["H"], s["d"]
    return {
        "input_layernorm.weight": ((H,), "gain"),
        "pre_ff_layernorm.weight": ((H,), "gain"),
        "self_attn.q_proj.weight": ((s["heads"] * d, H), "matrix"),
        "self_attn.k_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
        "self_attn.v_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
        "self_attn.o_proj.weight": ((H, s["heads"] * d), "matrix"),
        "mamba.in_proj.weight": ((s["in"], H), "matrix"),
        "mamba.conv1d.weight": ((s["conv_dim"], 1, s["conv"]), "conv"),
        "mamba.conv1d.bias": ((s["conv_dim"],), "conv_bias"),
        "mamba.A_log": ((s["ssm_heads"],), "a_log"),
        "mamba.dt_bias": ((s["ssm_heads"],), "dt_bias"),
        "mamba.D": ((s["ssm_heads"],), "ones"),
        "mamba.norm.weight": ((s["d_ssm"],), "gain"),
        "mamba.out_proj.weight": ((H, s["d_ssm"]), "matrix"),
        "feed_forward.gate_proj.weight": ((s["F"], H), "matrix"),
        "feed_forward.up_proj.weight": ((s["F"], H), "matrix"),
        "feed_forward.down_proj.weight": ((H, s["F"]), "matrix"),
    }


def path_multiplier(conf: Dict, name: str):
    """What the multipliers on a matrix's path come to, an OUTPUT (a
    float, or for ``in_proj`` a float32 column): the draw is divided by
    it.  Host arithmetic: the same number whatever program draws."""
    gate, down = (float(m) for m in conf["mlp_multipliers"])
    a_in = float(conf["attention_in_multiplier"])
    if name == "mamba.in_proj.weight":
        return float(conf["ssm_in_multiplier"]) * np.concatenate([
            np.full((w, 1), float(m)) for (_, w), m in zip(
                segments(conf), conf["ssm_multipliers"])])
    return {
        "self_attn.q_proj.weight": a_in,
        "self_attn.k_proj.weight": a_in * float(conf["key_multiplier"]),
        "self_attn.v_proj.weight": a_in,
        "self_attn.o_proj.weight": float(conf["attention_out_multiplier"]),
        "mamba.out_proj.weight": float(conf["ssm_out_multiplier"]),
        "feed_forward.gate_proj.weight": gate,
        "feed_forward.up_proj.weight": 1.0,
        "feed_forward.down_proj.weight": down,
        "model.embed_tokens.weight": float(conf["embedding_multiplier"]),
        "lm_head.weight": float(conf["lm_head_multiplier"]),
    }[name]


def matrix_scale(conf: Dict, name: str, fan_in: int):
    """The ONE factor a matrix's normal draw is multiplied by (a float32
    scalar or column, made on the host): a second device operation
    could round differently from one program to the next, and a leaf
    rounded to bfloat16 after it would then differ in a few places."""
    unit = 1.0 if name == _TOP[0] else fan_in ** -0.5
    return np.asarray(unit / path_multiplier(conf, name), np.float32)


def _rounded(x):
    return x.astype(jnp.bfloat16)


def _normal(key, shape):
    """A standard normal draw, float32, behind a barrier: the scale that
    follows is then ONE multiplication of these very numbers in every
    program.  Without it a compiler folds the draw's own last factor
    into the scale, the product differs in its last bit from one
    program to the next, and a leaf rounded to bfloat16 after it in one
    place in ten thousand (with matrices this large, that is seen)."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def draw_leaf(conf: Dict, key, name: str, shape, kind: str):
    """One leaf (``key``: the layer's key, or the top's): bfloat16
    (rounded after the draw), float32 for the kinds ``a_log``,
    ``dt_bias`` and ``ones``."""
    k = jax.random.fold_in(
        key, _ORDER.index(name) if name in _ORDER else 100 + _TOP.index(name))
    if kind == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, *A_RANGE))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, *(math.log(v) for v in DT_RANGE)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    x = _normal(k, shape)
    if kind == "gain":
        return _rounded(1.0 + GAIN_STD * x)
    if kind == "conv":
        return _rounded(x * np.float32(shape[-1] ** -0.5))
    if kind == "conv_bias":
        return _rounded(CONV_BIAS_STD * x)
    return _rounded(x * matrix_scale(conf, name, shape[-1]))


def layer_weights(conf: Dict, key, index: int) -> Dict:
    """Layer ``index`` in the published layout."""
    lk = layer_key(key, index)
    return {name: draw_leaf(conf, lk, name, shape, kind)
            for name, (shape, kind) in layer_leaves(conf).items()}


def top_weights(conf: Dict, key, rows=None) -> Dict:
    """The embedding, the final norm's gain and the head; ``rows``: of
    the first ``rows`` ids only (a slice of the vocabulary is its first
    rows: row ``i`` is the same whatever the slice)."""
    s = sizes(conf)
    tk = jax.random.fold_in(key, 0)
    V = s["V"] if rows is None else int(rows)

    def rows_of(name):
        # a row a key: the first quarter of the whole vocabulary's
        # matrix is the quarter's matrix
        k = jax.random.fold_in(tk, 100 + _TOP.index(name))
        scale = matrix_scale(conf, name, s["H"])
        return jax.lax.map(
            lambda i: _rounded(_normal(jax.random.fold_in(k, i), (s["H"],))
                               * scale),
            jnp.arange(V), batch_size=4096)

    return {_TOP[0]: rows_of(_TOP[0]),
            _TOP[1]: draw_leaf(conf, tk, _TOP[1], (s["H"],), "gain"),
            _TOP[2]: rows_of(_TOP[2])}
