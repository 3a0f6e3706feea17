"""Between the published GPT-2 layout (``cellbench/weights.py``) and
the tree ``apex_tpu.models.gpt`` runs: a rename, and a transpose of the
matrices (``apex_tpu`` computes ``x @ w.T``)."""

from typing import Dict

#: program leaf under params["layers"] -> (published block leaf, transpose?)
LAYERS = {
    "ln1_scale": ("ln_1.g", False), "ln1_bias": ("ln_1.b", False),
    "wq": ("attn.wq", True), "wk": ("attn.wk", True),
    "wv": ("attn.wv", True),
    "bq": ("attn.bq", False), "bk": ("attn.bk", False),
    "bv": ("attn.bv", False),
    "wo": ("attn.wo", True), "bo": ("attn.bo", False),
    "ln2_scale": ("ln_2.g", False), "ln2_bias": ("ln_2.b", False),
    "fc1": ("mlp.w_fc", True), "fc1_b": ("mlp.b_fc", False),
    "fc2": ("mlp.w_proj", True), "fc2_b": ("mlp.b_proj", False),
}
TOP = {"embed": "wte", "pos_embed": "wpe", "final_ln_scale": "ln_f.g",
       "final_ln_bias": "ln_f.b"}


def to_program_tree(w: Dict) -> Dict:
    out = {prog: w[pub] for prog, pub in TOP.items()}
    out["layers"] = {
        prog: (w["blocks"][pub].transpose(0, 2, 1) if tr
               else w["blocks"][pub])
        for prog, (pub, tr) in LAYERS.items()}
    return out


def to_published_tree(tree: Dict) -> Dict:
    """The inverse of :func:`to_program_tree`, for a program-shaped tree
    of arrays (NumPy or JAX)."""
    out = {pub: tree[prog] for prog, pub in TOP.items()}
    out["blocks"] = {
        pub: (tree["layers"][prog].transpose(0, 2, 1) if tr
              else tree["layers"][prog])
        for prog, (pub, tr) in LAYERS.items()}
    return out


def published_names(tree: Dict) -> Dict[str, object]:
    """A program-shaped tree of per-leaf numbers, flattened under the
    published names (norms do not care about a transpose)."""
    out = {pub: tree[prog] for prog, pub in TOP.items()}
    for prog, (pub, _) in LAYERS.items():
        out[f"blocks.{pub}"] = tree["layers"][prog]
    return out


def flatten_published(w: Dict) -> Dict[str, object]:
    out = {k: v for k, v in w.items() if k != "blocks"}
    for k, v in w["blocks"].items():
        out[f"blocks.{k}"] = v
    return out
