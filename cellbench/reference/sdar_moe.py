"""The plain reference for an ``sdar_moe`` decoder and for generation by
diffusion over blocks: grouped-query attention with an RMSNorm a head on
queries and keys, a softmax router over sparse experts in every layer
(the Qwen3-MoE block that ``sdar_moe`` derives from), attended under a
visibility mask that is causal BY BLOCK, and the released
``generate.py``'s procedure in plain Python over it (SDAR, arXiv:
2510.06303; the model card's ``generate.py``, as remembered).

Straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching.  Attention takes a DENSE visibility mask, one head at a time;
every HELD expert runs on every token and is weighted by its routing
weight or zero.  It imports nothing of ``apex_tpu``.  Weights come one
layer at a time in the published layout
(``cellbench/weights_sdar_moe.py``: ``y = x @ W.T``), are upcast here,
and are dropped before the next layer is made.

Per layer, ``h`` the residual stream, every norm an RMSNorm with a
gain::

    x  = norm(h; input_layernorm)
    q, k, v = x Wq, x Wk, x Wv      heads of head_dim; query head i reads
                                    key/value head i // group
    q, k = norm(q; q_norm), norm(k; k_norm)        a head, its channels
    q, k rotated over the whole head (halves), theta rope_theta
    A  = concat(softmax(q k^T / sqrt(head_dim), visible) v) Wo
    h  = h + A
    x2 = norm(h; post_attention_layernorm)
    p  = softmax(x2 Wr) over ALL experts; the top_k largest chosen
         (ties: the lowest id), w_e = p_e / sum_chosen p
    h  = h + sum_{e chosen and held} w_e (silu(x2 Wg_e) * (x2 Wu_e)) Wd_e

and ``logits = norm(h; norm) W_head``: **the logits at position i
predict the token AT position i** (a mask predicts itself; no shift).

Visibility: key ``j`` is visible to query ``i`` iff ``j // W <= i // W``
(:func:`block_causal`).  To check a served request in a few forwards the
sequence may be laid out as block diffusion is trained
(:func:`diffusion_layout`): ``[clean tokens ; the state of every
generated block at denoising pass t]``, a noisy block seeing the clean
blocks before it and itself, the clean part block-causal: one forward a
``t``.

Departures from the published description, each on purpose: (1) the
experts NOT held here add nothing (the chip's share; the configuration
file's ``deployment``); (2) the mask token's own row is left out of the
softmax and of the argmax (:func:`token_confidence`): a trained model
never predicts its mask, a seeded one would once in ``vocab`` rows, and
a position unmasked INTO a mask would never finish; (3) with a
temperature the confidence is the value of ``softmax(logits /
temperature)`` at the drawn token, and the draw itself is the caller's
(the tests drive greedy through this module).  What the published
``config.json`` is silent on is in the configuration file's ``assumed``.

``quant`` is the control that ``correct`` must reject: every matrix
multiplication's inputs (the attention's two products too) are rounded,
per tensor, to ``"float8_e4m3fn"`` (or, on the bits, to ``"bfloat16"``).
The router stays float32 either way, as the configuration states it.
"""

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# rounding ON THE BITS and the per-tensor float8 control
from cellbench.reference.evabyte import _quantizer, rope

NEG = -1e30
STRATEGIES = ("low_confidence_static", "low_confidence_dynamic",
              "sequential")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def attention(x, w: Dict, conf: Dict, positions, visible, q):
    """(S, H) normed input -> the attention branch's addition to the
    stream.  ``positions`` (S,): each row's position (its rotation);
    ``visible`` (S, S) bool: the keys a query row sees."""
    S = x.shape[0]
    heads, kv = int(conf["num_attention_heads"]), \
        int(conf["num_key_value_heads"])
    d, eps = int(conf["head_dim"]), float(conf["rms_norm_eps"])
    theta = float(conf["rope_theta"])
    mm = lambda a, wt: jnp.matmul(q(a), q(wt).T)
    qs = rms_norm(mm(x, w["self_attn.q_proj.weight"]).reshape(S, heads, d),
                  w["self_attn.q_norm.weight"], eps)
    ks = rms_norm(mm(x, w["self_attn.k_proj.weight"]).reshape(S, kv, d),
                  w["self_attn.k_norm.weight"], eps)
    qs, ks = rope(qs, positions, theta), rope(ks, positions, theta)
    vs = mm(x, w["self_attn.v_proj.weight"]).reshape(S, kv, d)
    shared = jnp.arange(heads) // (heads // kv)

    def head(args):
        q_i, g = args
        s = jnp.matmul(q(q_i), q(ks[:, g]).T) * d ** -0.5
        p = jax.nn.softmax(jnp.where(visible, s, NEG), axis=-1)
        return jnp.matmul(q(p), q(vs[:, g]))

    o = jax.lax.map(head, (jnp.moveaxis(qs, 1, 0), shared))
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, heads * d),
              w["self_attn.o_proj.weight"])


def route(x, router_w, top_k: int):
    """Softmax over ALL experts in float32, the ``top_k`` largest chosen
    (``lax.top_k``: ties to the lowest id), their weights renormalised
    over the chosen.  Returns the (S, E) matrix of routing weights, zero
    where an expert is not chosen."""
    p = jax.nn.softmax(jnp.matmul(x, router_w.T), axis=-1)
    picked, ids = jax.lax.top_k(p, top_k)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], ids] \
        .set(weights)


def experts(x, w: Dict, conf: Dict, held: range, q):
    """(S, H) normed input -> what the HELD experts add: every one of
    them on every token, weighted by its routing weight or zero."""
    weights = route(x, w["mlp.gate.weight"],
                    int(conf["num_experts_per_tok"]))[:, held.start:held.stop]

    def one(args):
        wg, wu, wd, w_e = args
        inner = jax.nn.silu(jnp.matmul(q(x), q(wg).T)) \
            * jnp.matmul(q(x), q(wu).T)
        return jnp.matmul(q(inner), q(wd).T) * w_e[:, None]

    return jnp.sum(jax.lax.map(one, (
        w["mlp.experts.gate_proj.weight"], w["mlp.experts.up_proj.weight"],
        w["mlp.experts.down_proj.weight"], weights.T)), axis=0)


def layer(h, w: Dict, conf: Dict, positions, visible, held: range,
          quant: Optional[str] = None, branches=("attention", "experts")):
    """One layer on the stream ``h`` (S, H); ``w`` in the published
    layout (any float dtype: upcast here).  ``branches``: what is added
    (both: the model; one left out: what a test compares a program
    without it to)."""
    q = _quantizer(quant)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(conf["rms_norm_eps"])
    if "attention" in branches:
        h = h + attention(rms_norm(h, w["input_layernorm.weight"], eps), w,
                          conf, positions, visible, q)
    if "experts" in branches:
        h = h + experts(rms_norm(h, w["post_attention_layernorm.weight"],
                                 eps), w, conf, held, q)
    return h


def embed(top: Dict, tokens):
    return top["model.embed_tokens.weight"].astype(jnp.float32)[tokens]


def head_logits(conf: Dict, top: Dict, h, quant: Optional[str] = None):
    """The stream's rows ``h`` (R, H) -> logits (R, V)."""
    q = _quantizer(quant)
    x = rms_norm(h, top["model.norm.weight"].astype(jnp.float32),
                 float(conf["rms_norm_eps"]))
    return jnp.matmul(q(x), q(top["lm_head.weight"].astype(jnp.float32)).T)


def logits_of(conf: Dict, top: Dict, layer_weights: Callable, held: range,
              tokens, positions, visible, rows=None,
              quant: Optional[str] = None, layer_fn=None):
    """Logits (len(rows), V) of ONE laid-out sequence: ``tokens`` (S,)
    at ``positions`` (S,) under ``visible`` (S, S), at ``rows`` (all
    where None).  ``layer_weights(i)`` makes layer ``i``'s weights when
    asked: one layer's weights live at a time.  ``layer_fn``: a jitted
    :func:`layer` to reuse, ``(h, w, positions, visible) -> h``."""
    with jax.default_matmul_precision("highest"):
        fn = layer_fn or (lambda h, w, p, v: layer(h, w, conf, p, v, held,
                                                   quant))
        h = embed(top, tokens)
        for i in range(int(conf["num_hidden_layers"])):
            w = layer_weights(i)
            h = fn(h, w, positions, visible)
            del w
        return head_logits(conf, top, h if rows is None else h[rows], quant)


# ----------------------------------------------------------- visibility
def block_causal(length: int, block: int) -> np.ndarray:
    """(length, length) bool: key ``j`` visible to query ``i`` iff ``j
    // block <= i // block``."""
    b = np.arange(length) // block
    return b[None, :] <= b[:, None]


def diffusion_layout(clean: int, starts: Sequence[int], block: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Positions and visibility of ``[clean tokens ; noisy blocks]``:
    ``clean`` rows at positions ``0 .. clean - 1``, block-causal among
    themselves and blind to the noisy part, then a noisy block of
    ``block`` rows for each of ``starts`` (its first position, a
    multiple of ``block``), which sees the clean rows BEFORE its own
    block and its own rows.  Returns ``(positions (S,), visible (S,
    S))``."""
    starts = np.asarray(starts, np.int64).reshape(-1)
    noisy = (starts[:, None] + np.arange(block)[None]).reshape(-1)
    positions = np.concatenate([np.arange(clean), noisy])
    S = positions.shape[0]
    visible = np.zeros((S, S), bool)
    visible[:clean, :clean] = block_causal(clean, block)
    owner = np.repeat(np.arange(len(starts)), block)
    visible[clean:, :clean] = np.arange(clean)[None, :] \
        < (noisy // block * block)[:, None]
    visible[clean:, clean:] = owner[:, None] == owner[None, :]
    return positions, visible


# ------------------------------------------------------------ generation
def token_confidence(logits, mask_id: int, temperature: float = 0.0):
    """Of logits (R, V): the greedy token a row and its confidence, the
    value of ``softmax`` at it in float32, with the mask's own row left
    out of both (module doc, departure 2).  With a ``temperature`` the
    softmax is of ``logits / temperature``; the token stays the
    argmax."""
    z = jnp.asarray(logits, jnp.float32).at[:, mask_id].set(NEG)
    if temperature > 0.0:
        z = z / temperature
    p = jax.nn.softmax(z, axis=-1)
    x0 = jnp.argmax(z, axis=-1)
    return np.asarray(x0), np.asarray(
        jnp.take_along_axis(p, x0[:, None], axis=-1)[:, 0])


def unmask_counts(block: int, steps: int) -> List[int]:
    """``n_t``: the masked positions pass ``t`` unmasks (at least)."""
    return [block // steps + (t < block % steps) for t in range(steps)]


def choose(masked: Sequence[bool], conf, n: int, strategy: str,
           threshold: float = 0.9) -> List[int]:
    """The positions of a block a denoising pass unmasks.
    ``low_confidence_static``: the ``n`` masked positions of highest
    confidence (ties: the lowest index); ``low_confidence_dynamic``:
    every masked position whose confidence passes ``threshold``, or the
    ``n`` best where fewer pass; ``sequential``: the leftmost ``n``."""
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy {strategy!r} is none of {STRATEGIES}")
    open_ = [i for i, m in enumerate(masked) if m]
    if strategy == "sequential":
        return open_[:n]
    best = sorted(open_, key=lambda i: (-float(conf[i]), i))[:n]
    if strategy == "low_confidence_static":
        return sorted(best)
    over = [i for i in open_ if float(conf[i]) > threshold]
    return over if len(over) >= n else sorted(best)


def generate(forward: Callable, prompt: Sequence[int], gen_length: int,
             block: int, steps: int, mask_id: int,
             strategy: str = "low_confidence_static",
             threshold: float = 0.9):
    """The released procedure, greedy, in plain Python.  ``forward(ids)
    -> logits (len(ids), V)`` is the model under the block-causal mask
    (what is stored of a block are its clean tokens' keys and values,
    so a forward of the clean prefix and the current block's state is
    what a pass sees).  The sequence is the prompt followed by
    ``gen_length`` masks, padded with masks to a multiple of ``block``;
    the whole blocks of the prompt are context, what is left of it
    opens the first block.  Returns ``(tokens, passes)``: the
    ``gen_length`` generated tokens, and every pass in order as
    ``(block start, ids before, chosen positions, ids after)``, a
    commit pass with no position chosen."""
    P = len(prompt)
    end = -(-(P + gen_length) // block) * block
    seq = list(prompt) + [mask_id] * (end - P)
    passes = []
    for start in range(P // block * block, end, block):
        t = 0
        while True:
            cur = seq[start:start + block]
            masked = [x == mask_id for x in cur]
            if not any(masked):     # the commit pass: keys and values
                passes.append((start, cur, [], cur))   # of clean tokens
                break
            logits = np.asarray(forward(seq[:start + block]))[start:]
            x0, conf = token_confidence(logits, mask_id)
            n = unmask_counts(block, steps)[t]
            for i in choose(masked, conf, n, strategy, threshold):
                seq[start + i] = int(x0[i])
            passes.append((start, cur, [i for i in range(block)
                                        if cur[i] != seq[start + i]],
                           seq[start:start + block]))
            t += 1
    return seq[P:P + gen_length], passes
