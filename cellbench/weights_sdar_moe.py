"""Seeded weights for an ``sdar_moe`` model (grouped-query attention with
a norm a head on queries and keys, a softmax router over sparse experts
in every layer), in the PUBLISHED layout and ONE LAYER at a time.

As ``cellbench/weights_mla_moe.py`` (whose key derivation this module
shares): ``layer_weights(conf, key, index)`` and ``top_weights(conf,
key)`` are pure functions of the configuration file, a PRNG key and the
layer's 0-based index; a ``weight`` is ``(out, in)``: ``y = x @ W.T``.
Every leaf is ROUNDED TO BFLOAT16 after its draw, so that a bf16 program
and a float32 reference hold the same numbers.  An expert's key is
folded from its id: expert 37 has the same weights whichever share of
the experts holds it.  The configuration's ``num_experts`` is the number
HELD here (ids ``held_start`` onwards, 0 unless the file says
otherwise); the router keeps its published width,
``published.num_experts`` in the file.

**Scales.**  A seeded model at N(0, 0.02) would leave attention and the
experts a hundredth of the stream, the logits a function of the token's
own embedding, and the comparison blind to both.  So: the embedding is
N(0, 1); a matrix N(0, 1 / fan_in) (unit variance in, unit variance
out); the head N(0, 1 / hidden) (logits of unit variance); the router
N(0, 1 / hidden) (logits of unit variance over 128 experts: the choice
is spread and the chosen weigh about an eighth each).  ``q_norm``'s gain
is :data:`QUERY_GAIN` + N(0, 0.02): scores of standard deviation 2, so
that a row's softmax rests on a few keys as a trained model's does and
what attention adds is of the stream's own order (at gain 1 it averages
hundreds of values to nothing).  The experts' ``down_proj`` is N(0, 1 /
fan_in) times :data:`EXPERT_OUT_GAIN`: this chip computes one in eight
of a token's chosen experts on average, each weighted about an eighth,
and the gain lets that share move the stream as the whole layer would.
Other gains are 1 + N(0, 0.02).
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.weights_mla_moe import layer_key, seed_key  # noqa: F401

GAIN_STD = 0.02
QUERY_GAIN = 2.0
EXPERT_OUT_GAIN = 4.0
_TOP = ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight")

#: every leaf of a layer, in key order (a leaf's key is folded from its
#: place here: adding one moves no other)
_ORDER = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.k_proj.weight", "self_attn.v_proj.weight",
    "self_attn.o_proj.weight", "self_attn.q_norm.weight",
    "self_attn.k_norm.weight", "post_attention_layernorm.weight",
    "mlp.gate.weight", "mlp.experts.gate_proj.weight",
    "mlp.experts.up_proj.weight", "mlp.experts.down_proj.weight",
)


def sizes(conf: Dict) -> Dict[str, int]:
    pub = conf.get("published", {})
    return {
        "V": int(conf["vocab_size"]), "H": int(conf["hidden_size"]),
        "L": int(conf["num_hidden_layers"]),
        "heads": int(conf["num_attention_heads"]),
        "kv_heads": int(conf["num_key_value_heads"]),
        "d": int(conf["head_dim"]),
        "Fe": int(conf["moe_intermediate_size"]),
        "held": int(conf["num_experts"]),
        "held_start": int(conf.get("cellbench", {}).get("held_start", 0)),
        "E": int(pub.get("num_experts", conf["num_experts"])),
        "top_k": int(conf["num_experts_per_tok"]),
    }


def held(conf: Dict) -> range:
    s = sizes(conf)
    return range(s["held_start"], s["held_start"] + s["held"])


def layer_leaves(conf: Dict) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of a layer's leaves (every layer is alike).
    Kinds: matrix, gain, query_gain, expert, expert_out (matrices with a
    leading held-experts axis)."""
    s = sizes(conf)
    H, d, n = s["H"], s["d"], s["held"]
    return {
        "input_layernorm.weight": ((H,), "gain"),
        "self_attn.q_proj.weight": ((s["heads"] * d, H), "matrix"),
        "self_attn.k_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
        "self_attn.v_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
        "self_attn.o_proj.weight": ((H, s["heads"] * d), "matrix"),
        "self_attn.q_norm.weight": ((d,), "query_gain"),
        "self_attn.k_norm.weight": ((d,), "gain"),
        "post_attention_layernorm.weight": ((H,), "gain"),
        "mlp.gate.weight": ((s["E"], H), "matrix"),
        "mlp.experts.gate_proj.weight": ((n, s["Fe"], H), "expert"),
        "mlp.experts.up_proj.weight": ((n, s["Fe"], H), "expert"),
        "mlp.experts.down_proj.weight": ((n, H, s["Fe"]), "expert_out"),
    }


def _rounded(x):
    return x.astype(jnp.bfloat16)


def _normal(key, shape):
    """A standard normal draw, float32, behind a barrier, so that the
    scale that follows is ONE multiplication of these very numbers in
    every program (``weights_falcon_h1._normal`` has the why)."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def draw_leaf(key, name: str, shape, kind: str, first_expert: int = 0):
    """One leaf (``key``: the layer's key, or the top's), bfloat16
    (rounded after the draw)."""
    k = jax.random.fold_in(
        key, _ORDER.index(name) if name in _ORDER else 100 + _TOP.index(name))
    if kind in ("expert", "expert_out"):
        scale = np.float32(shape[-1] ** -0.5 * (
            EXPERT_OUT_GAIN if kind == "expert_out" else 1.0))
        return jax.lax.map(
            lambda e: _rounded(_normal(jax.random.fold_in(k, e), shape[1:])
                               * scale),
            first_expert + jnp.arange(shape[0]))
    x = _normal(k, shape)
    if kind == "gain":
        return _rounded(1.0 + GAIN_STD * x)
    if kind == "query_gain":
        return _rounded(QUERY_GAIN + GAIN_STD * x)
    unit = 1.0 if name == _TOP[0] else shape[-1] ** -0.5
    return _rounded(x * np.float32(unit))


def layer_weights(conf: Dict, key, index: int) -> Dict:
    """Layer ``index`` in the published layout, bfloat16."""
    lk = layer_key(key, index)
    first = held(conf).start
    return {name: draw_leaf(lk, name, shape, kind, first)
            for name, (shape, kind) in layer_leaves(conf).items()}


def top_weights(conf: Dict, key) -> Dict:
    """The embedding, the final norm's gain and the head, a row a key:
    the first eighth of the whole vocabulary's matrix is the eighth's
    matrix."""
    s = sizes(conf)
    tk = jax.random.fold_in(key, 0)

    def rows_of(name):
        k = jax.random.fold_in(tk, 100 + _TOP.index(name))
        scale = np.float32(1.0 if name == _TOP[0] else s["H"] ** -0.5)
        return jax.lax.map(
            lambda i: _rounded(_normal(jax.random.fold_in(k, i), (s["H"],))
                               * scale),
            jnp.arange(s["V"]), batch_size=4096)

    return {_TOP[0]: rows_of(_TOP[0]),
            _TOP[1]: draw_leaf(tk, _TOP[1], (s["H"],), "gain"),
            _TOP[2]: rows_of(_TOP[2])}
