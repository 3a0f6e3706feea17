"""Seeded ``afmoe`` weights, made on the device, and the way between
their layout and the program's tree.

:func:`weights` is a pure function of the configuration and a PRNG key:
a flat dict under the published module names (``model.layers.N.…``),
float32, matrices ``(out, in)`` as ``nn.Linear`` stores them.  One
departure from the published layout: the experts a chip HOLDS are
stacked on a leading axis (``mlp.experts.gate_proj.weight``: (held, F,
H)) instead of one module an expert.  Matrices N(0, 0.02), gains 1
but for the norm after the mixer (:data:`POST_MIXER_GAIN`); the routers'
biases are not weights (they start at 0 in program and reference
alike).  Each leaf has its own key, folded from its name, so adding a
leaf never moves another's values.

**Why the gain of the norm after the mixer is 0.1.**  With seeded
matrices and unit gains a layer's attention is almost uniform (scores of
unit-normed heads have standard deviation 1 over up to 8,192 keys), so
every token's mixer output is the same average of values; the norm
after the mixer blows that up to unit size; and from the second layer
on every token chooses the same experts (first chip run of PR 38: the
most loaded expert of a layer got 15,841 of a step's 16,384 tokens,
the 16 held ones 141 each instead of 1,024).  A trained model does not
do that, and its balance rule keeps the load even.  Two seedings that
part the tokens again were tried: peaked attention (query and key
norms' gains 2: the load is even, 1,023 tokens a held expert, but a
near one-hot softmax over RANDOM scores flips its winner on a bfloat16
rounding, and the first gradient of the program then differs from the
float32 reference's by 0.6 of its norm: no limit of ``correct`` could
lie under a float8 control), and a small gain on the mixer's closing
norm, as residual branches are commonly born small.  The second is
kept: the stream stays the tokens' own, attention keeps benign
numerics, and what it computes is still compared leaf by leaf.

The adapter calls :func:`weights` inside one ``jit`` together with
:func:`to_program_tree`, so the program's tree is born on the device;
the plain reference calls it again from the same seed and never sees an
array the program has held.
"""

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
#: the seeded gain of ``post_attention_layernorm`` (above)
POST_MIXER_GAIN = 0.02


def router_width(conf: Dict) -> int:
    """The router's width: the PUBLISHED expert count (``num_experts``
    in the file is what this chip holds)."""
    return int(conf.get("published", {}).get("num_experts",
                                             conf["num_experts"]))


def shapes(conf: Dict) -> Dict[str, tuple]:
    """Published leaf name -> shape."""
    H, d = conf["hidden_size"], conf["head_dim"]
    nq = conf["num_attention_heads"] * d
    nkv = conf["num_key_value_heads"] * d
    inter, F = conf["intermediate_size"], conf["moe_intermediate_size"]
    Fs = F * conf["num_shared_experts"]
    held, V = conf["num_experts"], conf["vocab_size"]
    out = {"model.embed_tokens.weight": (V, H), "lm_head.weight": (V, H),
           "model.norm.weight": (H,)}
    for i in range(conf["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        for norm in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            out[f"{pre}{norm}.weight"] = (H,)
        att = pre + "self_attn."
        out.update({att + "q_proj.weight": (nq, H),
                    att + "k_proj.weight": (nkv, H),
                    att + "v_proj.weight": (nkv, H),
                    att + "gate_proj.weight": (nq, H),
                    att + "o_proj.weight": (H, nq),
                    att + "q_norm.weight": (d,),
                    att + "k_norm.weight": (d,)})
        mlp = pre + "mlp."
        if i < conf["num_dense_layers"]:
            out.update({mlp + "gate_proj.weight": (inter, H),
                        mlp + "up_proj.weight": (inter, H),
                        mlp + "down_proj.weight": (H, inter)})
        else:
            out.update({
                mlp + "router.gate.weight": (router_width(conf), H),
                mlp + "shared_experts.gate_proj.weight": (Fs, H),
                mlp + "shared_experts.up_proj.weight": (Fs, H),
                mlp + "shared_experts.down_proj.weight": (H, Fs),
                mlp + "experts.gate_proj.weight": (held, F, H),
                mlp + "experts.up_proj.weight": (held, F, H),
                mlp + "experts.down_proj.weight": (held, H, F)})
    return out


def weights(conf: Dict, key) -> Dict:
    out = {}
    for name, shape in shapes(conf).items():
        if len(shape) == 1:
            small = name.endswith("post_attention_layernorm.weight")
            out[name] = jnp.full(shape, POST_MIXER_GAIN if small else 1.0,
                                 jnp.float32)
        else:
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            out[name] = jax.random.normal(k, shape, jnp.float32) * STD
    return out


#: program leaf of a layer -> (published suffix, transpose the last two
#: axes?): the program stores matrices (in, out)
_LAYER = {
    "norm1": ("input_layernorm.weight", False),
    "norm2": ("post_attention_layernorm.weight", False),
    "norm3": ("pre_mlp_layernorm.weight", False),
    "norm4": ("post_mlp_layernorm.weight", False),
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wg": ("self_attn.gate_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
    "router": ("mlp.router.gate.weight", True),
    "ws_gate": ("mlp.shared_experts.gate_proj.weight", True),
    "ws_up": ("mlp.shared_experts.up_proj.weight", True),
    "ws_down": ("mlp.shared_experts.down_proj.weight", True),
    "we_gate": ("mlp.experts.gate_proj.weight", True),
    "we_up": ("mlp.experts.up_proj.weight", True),
    "we_down": ("mlp.experts.down_proj.weight", True),
}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}


def _pairs(conf: Dict):
    """(program path, published name, transpose?) of every leaf."""
    for prog, pub in _TOP.items():
        yield (prog,), pub, False
    names = shapes(conf)
    for i in range(conf["num_hidden_layers"]):
        for prog, (suffix, tr) in _LAYER.items():
            pub = f"model.layers.{i}.{suffix}"
            if pub in names:
                yield ("layers", i, prog), pub, tr


def to_program_tree(w: Dict, conf: Dict) -> Dict:
    """The optimizer's part of the program's tree (no ``"state"``) from
    a published-layout dict of arrays."""
    out = {"layers": [{} for _ in range(conf["num_hidden_layers"])]}
    for path, pub, tr in _pairs(conf):
        leaf = jnp.swapaxes(w[pub], -1, -2) if tr else w[pub]
        if len(path) == 1:
            out[path[0]] = leaf
        else:
            out["layers"][path[1]][path[2]] = leaf
    return out


def to_published(tree: Dict, conf: Dict, transpose: bool = True) -> Dict:
    """The inverse, for a program-shaped tree of arrays (NumPy or JAX),
    or of per-leaf numbers (``transpose=False``)."""
    out = {}
    for path, pub, tr in _pairs(conf):
        leaf = tree[path[0]] if len(path) == 1 else \
            tree["layers"][path[1]][path[2]]
        out[pub] = leaf.swapaxes(-1, -2) if tr and transpose else leaf
    return out


def is_expert(name: str) -> bool:
    """A routed expert's leaf or a router: what a flipped choice of
    expert moves."""
    return ".mlp.experts." in name or ".mlp.router." in name
