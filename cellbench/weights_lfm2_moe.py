"""Seeded weights for an ``lfm2_moe`` model (layers that mix by a gated
short convolution or by grouped-query attention with a norm a head on
queries and keys, over a dense or a sparse feed-forward with a sigmoid
router and a choice-only bias; tied embedding and head), in the
PUBLISHED layout and ONE LAYER at a time.

As ``cellbench/weights_sdar_moe.py`` (whose key derivation this module
shares): ``layer_weights(conf, key, index)`` and ``top_weights(conf,
key)`` are pure functions of the configuration file, a PRNG key and the
layer's 0-based index; a ``weight`` is ``(out, in)``: ``y = x @ W.T``.
Every leaf is ROUNDED TO BFLOAT16 after its draw, so that a bf16 program
and a float32 reference hold the same numbers.  WHICH leaves a layer has
follows from the file's ``layer_types`` (``conv`` or
``full_attention``) and ``num_dense_layers``.  An expert's key is folded
from its id; an expert's three matrices come stacked over the experts
(``feed_forward.experts.w1.weight`` ``(E, F, H)``), where the published
checkpoint keeps a module an expert.

**Scales** (seed what a trained model HAS: every branch moves the
stream, no expert takes most of the tokens, the head predicts from the
context).  The embedding is N(0, :data:`EMBED_STD`), small against the
stream as a trained model's (see below); a matrix N(0, 1 / fan_in)
(unit variance in, unit variance out: ``z = B * x``, a product of two
such, has unit variance too); the convolution's filter N(0, 1 / taps).
``q_layernorm``'s gain is :data:`QUERY_GAIN` + N(0, 0.02): scores of
standard deviation 2, so that a row's softmax rests on a few keys as a
trained model's does.  The router N(0, 1 / hidden): pre-sigmoid scores of unit variance, the choice spread over the
32 experts, the four chosen weighing about a quarter each;
``expert_bias`` N(0, :data:`BIAS_STD`): it moves the choice of about one
token in six and the load of no expert by more than a third (at 0.05
it moved two tokens in three and loads between a third and twice the
even share: the four best of 32 sigmoid scores lie a hundredth apart; a
trained model's bias is what balancing left behind, small against the
scores' spread).  The experts' ``w2`` is N(0, 1 / fan_in) times
:data:`EXPERT_OUT_GAIN`: four outputs of unit variance weighted a
quarter each sum to a standard deviation of a half, and the gain lets
the layer move the stream as a dense one does.  **The tied head.**  The
head's rows ARE the embedding's, so the stream's own copy of the
current token's embedding scores that token ``|E_tok|^2 / rms(h)``
above the rest: with an embedding of unit variance that is 9 standard
deviations of the logits at 13 layers, every step repeats its input and
no precision can move a token (the first chip run of PR 47: 3,236 of
3,236 served tokens the reference's first choice at a gap of exactly
0).  At :data:`EMBED_STD` 0.02 it is 0.2 of one, the first norm brings
the embedding back to unit scale for layer 1, and the logits are the
context's.  ``embedding_norm``'s gain is (1 + N(0, 0.02)) / (EMBED_STD
sqrt(hidden)): logits of unit variance, as an untied head of N(0, 1 /
hidden) would give.  Other gains are 1 + N(0, 0.02).
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cellbench.weights_mla_moe import layer_key, seed_key  # noqa: F401

GAIN_STD = 0.02
EMBED_STD = 0.02
QUERY_GAIN = 2.0
EXPERT_OUT_GAIN = 2.0
BIAS_STD = 0.01
_TOP = ("model.embed_tokens.weight", "model.embedding_norm.weight")

#: every leaf a layer may have, in key order (a leaf's key is folded from
#: its place here: adding one moves no other)
_ORDER = (
    "operator_norm.weight", "ffn_norm.weight",
    "conv.in_proj.weight", "conv.conv.weight", "conv.out_proj.weight",
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.out_proj.weight",
    "self_attn.q_layernorm.weight", "self_attn.k_layernorm.weight",
    "feed_forward.w1.weight", "feed_forward.w3.weight",
    "feed_forward.w2.weight",
    "feed_forward.gate.weight", "feed_forward.expert_bias",
    "feed_forward.experts.w1.weight", "feed_forward.experts.w3.weight",
    "feed_forward.experts.w2.weight",
)


def sizes(conf: Dict) -> Dict[str, int]:
    heads = int(conf["num_attention_heads"])
    kinds = list(conf["layer_types"])
    return {
        "V": int(conf["vocab_size"]), "H": int(conf["hidden_size"]),
        "L": int(conf["num_hidden_layers"]),
        "dense": int(conf["num_dense_layers"]),
        "heads": heads, "kv_heads": int(conf["num_key_value_heads"]),
        "d": int(conf["hidden_size"]) // heads,
        "F": int(conf["intermediate_size"]),
        "Fe": int(conf["moe_intermediate_size"]),
        "E": int(conf["num_experts"]),
        "top_k": int(conf["num_experts_per_tok"]),
        "K": int(conf["conv_L_cache"]),
        "conv_layers": kinds.count("conv"),
        "attn_layers": len(kinds) - kinds.count("conv"),
    }


def layer_leaves(conf: Dict, index: int) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of layer ``index``'s leaves.  Kinds:
    matrix, gain, query_gain, filter, bias, expert, expert_out (the last
    two: matrices with a leading experts axis)."""
    s = sizes(conf)
    H, d, E = s["H"], s["d"], s["E"]
    out = {"operator_norm.weight": ((H,), "gain"),
           "ffn_norm.weight": ((H,), "gain")}
    if conf["layer_types"][index] == "conv":
        out.update({
            "conv.in_proj.weight": ((3 * H, H), "matrix"),
            "conv.conv.weight": ((H, 1, s["K"]), "filter"),
            "conv.out_proj.weight": ((H, H), "matrix")})
    else:
        out.update({
            "self_attn.q_proj.weight": ((s["heads"] * d, H), "matrix"),
            "self_attn.k_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
            "self_attn.v_proj.weight": ((s["kv_heads"] * d, H), "matrix"),
            "self_attn.out_proj.weight": ((H, s["heads"] * d), "matrix"),
            "self_attn.q_layernorm.weight": ((d,), "query_gain"),
            "self_attn.k_layernorm.weight": ((d,), "gain")})
    if index < s["dense"]:
        out.update({
            "feed_forward.w1.weight": ((s["F"], H), "matrix"),
            "feed_forward.w3.weight": ((s["F"], H), "matrix"),
            "feed_forward.w2.weight": ((H, s["F"]), "matrix")})
    else:
        out.update({
            "feed_forward.gate.weight": ((E, H), "matrix"),
            "feed_forward.expert_bias": ((E,), "bias"),
            "feed_forward.experts.w1.weight": ((E, s["Fe"], H), "expert"),
            "feed_forward.experts.w3.weight": ((E, s["Fe"], H), "expert"),
            "feed_forward.experts.w2.weight": ((E, H, s["Fe"]),
                                               "expert_out")})
    return out


def _rounded(x):
    return x.astype(jnp.bfloat16)


def _normal(key, shape):
    """A standard normal draw, float32, behind a barrier, so that the
    scale that follows is ONE multiplication of these very numbers in
    every program (``weights_falcon_h1._normal`` has the why)."""
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


def draw_leaf(key, name: str, shape, kind: str):
    """One leaf (``key``: the layer's key, or the top's), bfloat16
    (rounded after the draw)."""
    k = jax.random.fold_in(
        key, _ORDER.index(name) if name in _ORDER else 100 + _TOP.index(name))
    if kind in ("expert", "expert_out"):
        scale = np.float32(shape[-1] ** -0.5 * (
            EXPERT_OUT_GAIN if kind == "expert_out" else 1.0))
        return jax.lax.map(
            lambda e: _rounded(_normal(jax.random.fold_in(k, e), shape[1:])
                               * scale), jnp.arange(shape[0]))
    x = _normal(k, shape)
    if kind == "gain":
        return _rounded(1.0 + GAIN_STD * x)
    if kind == "query_gain":
        return _rounded(QUERY_GAIN + GAIN_STD * x)
    if kind == "final_gain":
        return _rounded((1.0 + GAIN_STD * x)
                        * np.float32(shape[-1] ** -0.5 / EMBED_STD))
    if kind == "bias":
        return _rounded(np.float32(BIAS_STD) * x)
    return _rounded(x * np.float32(shape[-1] ** -0.5))     # matrix, filter


def like(conf: Dict, index: int) -> int:
    """The first layer that has the leaves layer ``index`` has."""
    kind = lambda i: (conf["layer_types"][i] == "conv",
                      i < int(conf["num_dense_layers"]))
    return next(i for i in range(index + 1) if kind(i) == kind(index))


def layer_weights(conf: Dict, key, index, shaped_like=None) -> Dict:
    """Layer ``index`` in the published layout, bfloat16.  With
    ``shaped_like`` (a layer with the same leaves: :func:`like`) the
    index may be traced, so that one compiled draw serves every layer of
    a kind."""
    lk = layer_key(key, index)
    at = index if shaped_like is None else shaped_like
    return {name: draw_leaf(lk, name, shape, kind)
            for name, (shape, kind) in layer_leaves(conf, at).items()}


def top_weights(conf: Dict, key) -> Dict:
    """The embedding (which is the head: tied), a row a key, and the
    final norm's gain."""
    s = sizes(conf)
    tk = jax.random.fold_in(key, 0)
    k = jax.random.fold_in(tk, 100)
    rows = jax.lax.map(
        lambda i: _rounded(_normal(jax.random.fold_in(k, i), (s["H"],))
                           * np.float32(EMBED_STD)),
        jnp.arange(s["V"]), batch_size=4096)
    return {_TOP[0]: rows,
            _TOP[1]: draw_leaf(tk, _TOP[1], (s["H"],), "final_gain")}
