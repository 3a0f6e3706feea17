"""Seeded weights for a ``kimi_linear``-style model (KDA and MLA mixers
by a per-layer pattern, one leading dense layer, then sparse experts),
in the PUBLISHED layout and ONE LAYER at a time.

As ``cellbench/weights_mla_moe.py`` (whose key derivation this module
shares): ``layer_weights(conf, key, index)`` and ``top_weights(conf,
key)`` are pure functions of the configuration file, a PRNG key and the
layer's 0-based index; a ``weight`` is ``(out, in)``: ``y = x @ W.T``.
Matrices N(0, 0.02), norm gains 1 + N(0, 0.02), the router's
``e_score_correction_bias`` N(0, 0.01), a short convolution's filter
N(0, 0.3) (``(channels, 1, taps)``), all ROUNDED TO BFLOAT16 so that a
bf16 program and a float32 reference hold the same numbers.  Two leaves
stay float32, as the published checkpoints keep them: ``A_log`` and
``dt_bias``, drawn so that a channel's decay ``alpha = exp(-exp(A_log)
softplus(dt_bias))`` at a zero input is log-uniform in ``-ln(alpha)``
over (0.9, 0.9999), channel by channel: ``exp(A_log)`` log-uniform in
[1, 16] a head, ``dt_bias`` solved from the channel's target.

The configuration's ``num_experts`` is the number HELD here (ids
``held_start`` onwards); the router keeps its published width,
``published.num_experts`` in the file.
"""

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from cellbench.weights_mla_moe import layer_key, seed_key  # noqa: F401

STD = 0.02
BIAS_STD = 0.01
CONV_STD = 0.3
ALPHA_RANGE = (0.9, 0.9999)
_TOP = ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight")

#: every leaf name a layer of any kind can have, in key order (a leaf's
#: key is folded from its place here: adding one moves no other)
_ORDER = (
    "input_layernorm.weight", "post_attention_layernorm.weight",
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.q_conv1d.weight",
    "self_attn.k_conv1d.weight", "self_attn.v_conv1d.weight",
    "self_attn.A_log", "self_attn.dt_bias", "self_attn.f_a_proj.weight",
    "self_attn.f_b_proj.weight", "self_attn.b_proj.weight",
    "self_attn.g_a_proj.weight", "self_attn.g_b_proj.weight",
    "self_attn.o_norm.weight", "self_attn.o_proj.weight",
    "self_attn.kv_a_proj_with_mqa.weight",
    "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
    "block_sparse_moe.gate.weight",
    "block_sparse_moe.gate.e_score_correction_bias",
    "block_sparse_moe.experts.w1.weight",
    "block_sparse_moe.experts.w3.weight",
    "block_sparse_moe.experts.w2.weight",
    "block_sparse_moe.shared_experts.gate_proj.weight",
    "block_sparse_moe.shared_experts.up_proj.weight",
    "block_sparse_moe.shared_experts.down_proj.weight",
)


def sizes(conf: Dict) -> Dict[str, int]:
    pub = conf.get("published", {})
    lin = conf["linear_attn_config"]
    return {
        "V": int(conf["vocab_size"]), "H": int(conf["hidden_size"]),
        "L": int(conf["num_hidden_layers"]),
        "dense": int(conf["first_k_dense_replace"]),
        "heads": int(conf["num_attention_heads"]),
        "kv_rank": int(conf["kv_lora_rank"]),
        "nope": int(conf["qk_nope_head_dim"]),
        "rope": int(conf["qk_rope_head_dim"]), "v": int(conf["v_head_dim"]),
        "F": int(conf["intermediate_size"]),
        "Fe": int(conf["moe_intermediate_size"]),
        "Fs": int(conf["moe_intermediate_size"])
        * int(conf["num_shared_experts"]),
        "held": int(conf["num_experts"]),
        "held_start": int(conf.get("cellbench", {}).get("held_start", 0)),
        "E": int(pub.get("num_experts", conf["num_experts"])),
        "top_k": int(conf["num_experts_per_token"]),
        "kda_heads": int(lin["num_heads"]), "kda_d": int(lin["head_dim"]),
        "conv": int(lin["short_conv_kernel_size"]),
    }


def held(conf: Dict) -> range:
    s = sizes(conf)
    return range(s["held_start"], s["held_start"] + s["held"])


def is_kda(conf: Dict, index: int) -> bool:
    """Whether layer ``index`` (0-based) mixes by KDA (the config's
    lists are 1-based)."""
    return index + 1 in conf["linear_attn_config"]["kda_layers"]


def layer_leaves(conf: Dict, index: int) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of layer ``index``'s leaves.  Kinds:
    matrix, gain, bias, conv, a_log, dt_bias, expert (a matrix with a
    leading held-experts axis)."""
    s = sizes(conf)
    H = s["H"]
    out = {"input_layernorm.weight": ((H,), "gain"),
           "post_attention_layernorm.weight": ((H,), "gain")}
    if is_kda(conf, index):
        P, d = s["kda_heads"] * s["kda_d"], s["kda_d"]
        for n in "qkv":
            out[f"self_attn.{n}_proj.weight"] = ((P, H), "matrix")
            out[f"self_attn.{n}_conv1d.weight"] = ((P, 1, s["conv"]), "conv")
        out.update({
            "self_attn.A_log": ((s["kda_heads"],), "a_log"),
            "self_attn.dt_bias": ((P,), "dt_bias"),
            "self_attn.f_a_proj.weight": ((d, H), "matrix"),
            "self_attn.f_b_proj.weight": ((P, d), "matrix"),
            "self_attn.b_proj.weight": ((s["kda_heads"], H), "matrix"),
            "self_attn.g_a_proj.weight": ((d, H), "matrix"),
            "self_attn.g_b_proj.weight": ((P, d), "matrix"),
            "self_attn.o_norm.weight": ((d,), "gain"),
            "self_attn.o_proj.weight": ((H, P), "matrix"),
        })
    else:
        heads = s["heads"]
        out.update({
            "self_attn.q_proj.weight": (
                (heads * (s["nope"] + s["rope"]), H), "matrix"),
            "self_attn.kv_a_proj_with_mqa.weight": (
                (s["kv_rank"] + s["rope"], H), "matrix"),
            "self_attn.kv_a_layernorm.weight": ((s["kv_rank"],), "gain"),
            "self_attn.kv_b_proj.weight": (
                (heads * (s["nope"] + s["v"]), s["kv_rank"]), "matrix"),
            "self_attn.o_proj.weight": ((H, heads * s["v"]), "matrix"),
        })
    if index < s["dense"]:
        out.update({
            "mlp.gate_proj.weight": ((s["F"], H), "matrix"),
            "mlp.up_proj.weight": ((s["F"], H), "matrix"),
            "mlp.down_proj.weight": ((H, s["F"]), "matrix"),
        })
    else:
        n, moe = s["held"], "block_sparse_moe."
        out.update({
            moe + "gate.weight": ((s["E"], H), "matrix"),
            moe + "gate.e_score_correction_bias": ((s["E"],), "bias"),
            moe + "experts.w1.weight": ((n, s["Fe"], H), "expert"),
            moe + "experts.w3.weight": ((n, s["Fe"], H), "expert"),
            moe + "experts.w2.weight": ((n, H, s["Fe"]), "expert"),
            moe + "shared_experts.gate_proj.weight": ((s["Fs"], H),
                                                      "matrix"),
            moe + "shared_experts.up_proj.weight": ((s["Fs"], H), "matrix"),
            moe + "shared_experts.down_proj.weight": ((H, s["Fs"]),
                                                      "matrix"),
        })
    return out


def _rounded(x):
    return x.astype(jnp.bfloat16)


def _decay_scale(key, heads: int):
    """``exp(A_log)``, (heads,): log-uniform in [1, 16]."""
    return jnp.exp(jax.random.uniform(key, (heads,), jnp.float32, 0.0,
                                      math.log(16.0)))


def draw_leaf(key, name: str, shape, kind: str, first_expert: int = 0,
              heads: int = 0):
    """One leaf of a layer (``key``: the layer's key): bfloat16 (values
    rounded after the draw), float32 for the kinds ``a_log`` and
    ``dt_bias`` (module doc; ``dt_bias`` is solved against the SAME
    layer's ``A_log`` over its ``heads``)."""
    fold = lambda n: jax.random.fold_in(
        key, _ORDER.index(n) if n in _ORDER else 100 + _TOP.index(n))
    if kind == "expert":
        ids = first_expert + jnp.arange(shape[0])
        return jax.lax.map(
            lambda e: _rounded(jax.random.normal(
                jax.random.fold_in(fold(name), e), shape[1:], jnp.float32)
                * STD), ids)
    if kind == "a_log":
        return jnp.log(_decay_scale(fold(name), shape[0]))
    if kind == "dt_bias":
        scale = _decay_scale(fold("self_attn.A_log"), heads)
        lo, hi = (-math.log(a) for a in ALPHA_RANGE[::-1])
        target = jnp.exp(jax.random.uniform(
            fold(name), shape, jnp.float32, math.log(lo), math.log(hi)))
        return jnp.log(jnp.expm1(
            target / jnp.repeat(scale, shape[0] // heads)))
    x = jax.random.normal(fold(name), shape, jnp.float32)
    if kind == "gain":
        return _rounded(1.0 + STD * x)
    return _rounded({"bias": BIAS_STD, "conv": CONV_STD}.get(kind, STD) * x)


def layer_weights(conf: Dict, key, index: int) -> Dict:
    """Layer ``index`` in the published layout."""
    lk = layer_key(key, index)
    first = held(conf).start
    heads = sizes(conf)["kda_heads"]
    return {name: draw_leaf(lk, name, shape, kind, first, heads)
            for name, (shape, kind) in layer_leaves(conf, index).items()}


def top_weights(conf: Dict, key) -> Dict:
    s = sizes(conf)
    tk = jax.random.fold_in(key, 0)
    return {
        "model.embed_tokens.weight": draw_leaf(
            tk, _TOP[0], (s["V"], s["H"]), "matrix"),
        "model.norm.weight": draw_leaf(tk, _TOP[1], (s["H"],), "gain"),
        "lm_head.weight": draw_leaf(tk, _TOP[2], (s["V"], s["H"]), "matrix"),
    }
