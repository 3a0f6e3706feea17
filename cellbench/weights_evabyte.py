"""Seeded weights for an ``evabyte`` model (EVA attention, a gated-SiLU
MLP, a head of ``num_pred_heads`` x ``vocab_size`` rows), in the
PUBLISHED layout and ONE LAYER at a time.

As ``cellbench/weights_mla_moe.py`` (whose key derivation this module
shares): ``layer_weights(conf, key, index)`` and ``top_weights(conf,
key)`` are pure functions of the configuration file, a PRNG key and the
layer's 0-based index; a ``weight`` is ``(out, in)``: ``y = x @ W.T``.
Matrices ``N(0, init_std)`` (the published 0.01275), norm gains ``g ~
N(0, 0.02)`` (the norm multiplies by ``1 + g``:
``norm_add_unit_offset``), the pooling direction ``adaptive_phi`` and
the key offset ``adaptive_mu_k`` ``N(0, 1)`` clipped to one and scaled
by ``head_dim ** -0.5`` (``(1, heads, 1, head_dim)``, the released
modelling code's shape), all ROUNDED TO BFLOAT16 so that a bf16 program
and a float32 reference hold the same numbers.  ``lm_head.weight`` is
head-major: row ``i * vocab_size + byte`` is head ``i``'s (head ``i``
predicts byte ``t + 1 + i``).
"""

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from cellbench.weights_mla_moe import layer_key, seed_key  # noqa: F401

GAIN_STD = 0.02
_TOP = ("model.embed_tokens.weight", "model.norm.weight", "lm_head.weight")
_ORDER = (
    "input_layernorm.weight", "post_attention_layernorm.weight",
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "self_attn.adaptive_phi", "self_attn.adaptive_mu_k",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
)


def sizes(conf: Dict) -> Dict[str, int]:
    H, heads = int(conf["hidden_size"]), int(conf["num_attention_heads"])
    return {
        "V": int(conf["vocab_size"]), "H": H,
        "L": int(conf["num_hidden_layers"]), "heads": heads,
        "d": H // heads, "F": int(conf["intermediate_size"]),
        "pred": int(conf["num_pred_heads"]),
        "window": int(conf["window_size"]), "chunk": int(conf["chunk_size"]),
    }


def layer_leaves(conf: Dict) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of a layer's leaves (every layer alike).
    Kinds: matrix, gain, pool."""
    s = sizes(conf)
    H, F = s["H"], s["F"]
    out = {"input_layernorm.weight": ((H,), "gain"),
           "post_attention_layernorm.weight": ((H,), "gain")}
    for n in "qkvo":
        out[f"self_attn.{n}_proj.weight"] = ((H, H), "matrix")
    pool = (1, s["heads"], 1, s["d"])
    out.update({
        "self_attn.adaptive_phi": (pool, "pool"),
        "self_attn.adaptive_mu_k": (pool, "pool"),
        "mlp.gate_proj.weight": ((F, H), "matrix"),
        "mlp.up_proj.weight": ((F, H), "matrix"),
        "mlp.down_proj.weight": ((H, F), "matrix"),
    })
    return out


def draw_leaf(conf: Dict, key, name: str, shape, kind: str):
    """One leaf (``key``: its layer's key, or the top's): bfloat16,
    rounded after the draw."""
    k = jax.random.fold_in(
        key, _ORDER.index(name) if name in _ORDER else 100 + _TOP.index(name))
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "gain":
        x = GAIN_STD * x
    elif kind == "pool":
        x = jnp.clip(x, -1.0, 1.0) * shape[-1] ** -0.5
    else:
        x = float(conf["init_std"]) * x
    return x.astype(jnp.bfloat16)


def layer_weights(conf: Dict, key, index: int) -> Dict:
    """Layer ``index`` in the published layout."""
    lk = layer_key(key, index)
    return {name: draw_leaf(conf, lk, name, shape, kind)
            for name, (shape, kind) in layer_leaves(conf).items()}


def top_weights(conf: Dict, key) -> Dict:
    s = sizes(conf)
    tk = jax.random.fold_in(key, 0)
    return {
        _TOP[0]: draw_leaf(conf, tk, _TOP[0], (s["V"], s["H"]), "matrix"),
        _TOP[1]: draw_leaf(conf, tk, _TOP[1], (s["H"],), "gain"),
        _TOP[2]: draw_leaf(conf, tk, _TOP[2], (s["pred"] * s["V"], s["H"]),
                           "matrix"),
    }
