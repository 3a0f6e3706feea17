"""Seeded weights for a ``deepseek_v3``-style model (latent attention,
a leading dense stack, then sparse experts), in the PUBLISHED layout
and ONE LAYER at a time.

``layer_weights(conf, key, index)`` and ``top_weights(conf, key)`` are
pure functions of the configuration file, a PRNG key and the layer's
index; every leaf is named as the published checkpoint names it and
shaped as it (``weight`` is ``(out, in)``: ``y = x @ W.T``).  Values
are drawn in float32 (matrices N(0, 0.02), norm gains 1 + N(0, 0.02),
``e_score_correction_bias`` N(0, 0.01)) and then ROUNDED TO BFLOAT16,
so that a program holding bf16 weights and a float32 reference hold the
same numbers.  Each leaf's key is folded from the layer's index and the
leaf's place in a fixed order, and each expert's from its id besides:
expert 37 has the same weights whichever share of the experts holds it,
and adding a leaf moves no other.

The configuration's ``n_routed_experts`` is the number HELD here (ids
``held_start`` onwards, 0 unless the file says otherwise); the router
keeps its published width, ``published.n_routed_experts`` in the file.
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

STD = 0.02
BIAS_STD = 0.01
_TOP = ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight")


def sizes(conf: Dict) -> Dict[str, int]:
    pub = conf.get("published", {})
    heads = int(conf["num_attention_heads"])
    return {
        "V": int(conf["vocab_size"]), "H": int(conf["hidden_size"]),
        "L": int(conf["num_hidden_layers"]),
        "dense": int(conf["first_k_dense_replace"]), "heads": heads,
        "q_rank": int(conf["q_lora_rank"]),
        "kv_rank": int(conf["kv_lora_rank"]),
        "nope": int(conf["qk_nope_head_dim"]),
        "rope": int(conf["qk_rope_head_dim"]), "v": int(conf["v_head_dim"]),
        "F": int(conf["intermediate_size"]),
        "Fe": int(conf["moe_intermediate_size"]),
        "Fs": int(conf["moe_intermediate_size"])
        * int(conf["n_shared_experts"]),
        "held": int(conf["n_routed_experts"]),
        "held_start": int(conf.get("cellbench", {}).get("held_start", 0)),
        "E": int(pub.get("n_routed_experts", conf["n_routed_experts"])),
        "top_k": int(conf["num_experts_per_tok"]),
    }


def held(conf: Dict) -> range:
    s = sizes(conf)
    return range(s["held_start"], s["held_start"] + s["held"])


def layer_leaves(conf: Dict, index: int) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of layer ``index``'s leaves, in the fixed
    order the keys are folded in.  Kinds: matrix, gain, bias, expert
    (a matrix with a leading held-experts axis)."""
    s = sizes(conf)
    H, heads = s["H"], s["heads"]
    out = {
        "input_layernorm.weight": ((H,), "gain"),
        "self_attn.q_a_proj.weight": ((s["q_rank"], H), "matrix"),
        "self_attn.q_a_layernorm.weight": ((s["q_rank"],), "gain"),
        "self_attn.q_b_proj.weight": (
            (heads * (s["nope"] + s["rope"]), s["q_rank"]), "matrix"),
        "self_attn.kv_a_proj_with_mqa.weight": (
            (s["kv_rank"] + s["rope"], H), "matrix"),
        "self_attn.kv_a_layernorm.weight": ((s["kv_rank"],), "gain"),
        "self_attn.kv_b_proj.weight": (
            (heads * (s["nope"] + s["v"]), s["kv_rank"]), "matrix"),
        "self_attn.o_proj.weight": ((H, heads * s["v"]), "matrix"),
        "post_attention_layernorm.weight": ((H,), "gain"),
    }
    if index < s["dense"]:
        out.update({
            "mlp.gate_proj.weight": ((s["F"], H), "matrix"),
            "mlp.up_proj.weight": ((s["F"], H), "matrix"),
            "mlp.down_proj.weight": ((H, s["F"]), "matrix"),
        })
    else:
        n = s["held"]
        out.update({
            "mlp.gate.weight": ((s["E"], H), "matrix"),
            "mlp.gate.e_score_correction_bias": ((s["E"],), "bias"),
            "mlp.experts.gate_proj.weight": ((n, s["Fe"], H), "expert"),
            "mlp.experts.up_proj.weight": ((n, s["Fe"], H), "expert"),
            "mlp.experts.down_proj.weight": ((n, H, s["Fe"]), "expert"),
            "mlp.shared_experts.gate_proj.weight": ((s["Fs"], H), "matrix"),
            "mlp.shared_experts.up_proj.weight": ((s["Fs"], H), "matrix"),
            "mlp.shared_experts.down_proj.weight": ((H, s["Fs"]), "matrix"),
        })
    return out


#: every leaf name a layer of either kind can have, in key order
_ORDER = (
    "input_layernorm.weight", "self_attn.q_a_proj.weight",
    "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
    "self_attn.kv_a_proj_with_mqa.weight",
    "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
    "self_attn.o_proj.weight", "post_attention_layernorm.weight",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
    "mlp.gate.weight", "mlp.gate.e_score_correction_bias",
    "mlp.experts.gate_proj.weight", "mlp.experts.up_proj.weight",
    "mlp.experts.down_proj.weight", "mlp.shared_experts.gate_proj.weight",
    "mlp.shared_experts.up_proj.weight",
    "mlp.shared_experts.down_proj.weight",
)


def _rounded(x):
    return x.astype(jnp.bfloat16)


def draw_leaf(key, name: str, shape, kind: str, first_expert: int = 0):
    """One leaf, bfloat16 (values rounded after the draw)."""
    key = jax.random.fold_in(key, _ORDER.index(name) if name in _ORDER
                             else 100 + _TOP.index(name))
    if kind == "expert":
        ids = first_expert + jnp.arange(shape[0])
        return jax.lax.map(
            lambda e: _rounded(jax.random.normal(
                jax.random.fold_in(key, e), shape[1:], jnp.float32) * STD),
            ids)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "gain":
        return _rounded(1.0 + STD * x)
    return _rounded((BIAS_STD if kind == "bias" else STD) * x)


def layer_key(key, index: int):
    return jax.random.fold_in(jax.random.fold_in(key, 1), index)


def layer_weights(conf: Dict, key, index: int) -> Dict:
    """Layer ``index`` in the published layout, bfloat16."""
    lk = layer_key(key, index)
    first = held(conf).start
    return {name: draw_leaf(lk, name, shape, kind, first)
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


def seed_key(seed: int):
    """A PRNG key from ``--seed`` (any whole number up to a little over
    2**31, folded in twice as ``cellbench.weights.seed_key`` does).  The
    key's implementation is ``rbg``: the device's own bit generator
    draws 5 G values in seconds where the default counter-based one
    took 54 s of every run's set-up (my chip run, PR 26).  Program and
    reference draw through this module in one process on one backend,
    which is all that the implementation's values are promised for."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"),
        (seed >> 31) & 0x7FFFFFFF)
