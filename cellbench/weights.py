"""Seeded GPT-2 weights, made on the device.

``gpt2_weights`` is a pure function of the sizes and a PRNG key, in the
published layout (``y = x @ W + b``, blocks stacked on a leading layer
axis).  The adapters call it inside one ``jit`` together with the
re-layout the program wants, so the program's tree is born on the
device, sharded where the adapter says; the plain reference calls it
again from the same seed and never sees an array the program has held.
"""

import math
from typing import Dict

import jax
import jax.numpy as jnp

#: leaf name -> (shape in terms of the sizes, kind)
_BLOCK = {
    "ln_1.g": ("H", "gain"), "ln_1.b": ("H", "bias"),
    "attn.wq": ("HH", "matrix"), "attn.wk": ("HH", "matrix"),
    "attn.wv": ("HH", "matrix"),
    "attn.bq": ("H", "bias"), "attn.bk": ("H", "bias"),
    "attn.bv": ("H", "bias"),
    "attn.wo": ("HH", "resid"), "attn.bo": ("H", "bias"),
    "ln_2.g": ("H", "gain"), "ln_2.b": ("H", "bias"),
    "mlp.w_fc": ("HF", "matrix"), "mlp.b_fc": ("F", "bias"),
    "mlp.w_proj": ("FH", "resid"), "mlp.b_proj": ("H", "bias"),
}
STD = 0.02


def sizes(model: Dict) -> Dict[str, int]:
    H = int(model["n_embd"])
    return {"H": H, "F": int(model.get("n_inner") or 4 * H),
            "L": int(model["n_layer"]), "V": int(model["vocab_size"]),
            "P": int(model["n_positions"]), "heads": int(model["n_head"])}


def _draw(key, shape, kind, n_layer):
    x = jax.random.normal(key, shape, jnp.float32) * STD
    if kind == "resid":
        return x / math.sqrt(2.0 * n_layer)
    if kind == "gain":
        return 1.0 + x
    return x


def gpt2_weights(model: Dict, key) -> Dict:
    """{"wte", "wpe", "ln_f.g", "ln_f.b", "blocks": {name: (L, ...)}},
    all float32.  Each leaf has its own key folded from its position in
    a fixed order, so adding a leaf never moves another's values."""
    s = sizes(model)
    L = s["L"]
    names = ["wte", "wpe", "ln_f.g", "ln_f.b"] + sorted(_BLOCK)
    keys = {n: jax.random.fold_in(key, i) for i, n in enumerate(names)}
    out = {
        "wte": _draw(keys["wte"], (s["V"], s["H"]), "matrix", L),
        "wpe": _draw(keys["wpe"], (s["P"], s["H"]), "matrix", L),
        "ln_f.g": _draw(keys["ln_f.g"], (s["H"],), "gain", L),
        "ln_f.b": _draw(keys["ln_f.b"], (s["H"],), "bias", L),
        "blocks": {},
    }
    for name, (dims, kind) in _BLOCK.items():
        shape = (L,) + tuple(s[d] for d in dims)
        out["blocks"][name] = _draw(keys[name], shape, kind, L)
    return out


def seed_key(seed: int):
    """A PRNG key from ``--seed`` (any whole number up to a little over
    2**31: folded into 32 bits twice, so no seed overflows)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
