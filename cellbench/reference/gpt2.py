"""The plain reference for GPT-2 (Radford et al. 2019).

Forward pass, cross-entropy loss, gradients by ``jax.grad`` and AdamW
(Loshchilov & Hutter 2019) in straightforward float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``: no kernels, no
cache, no batching tricks.  Written from the published description:
learned position embeddings, pre-LayerNorm blocks, biased projections,
``gelu_new`` (the tanh approximation), causal softmax attention scaled
by 1/sqrt(head size), a final LayerNorm and a head tied to the token
embedding.  It imports nothing of ``apex_tpu``.

Weights come in the layout of ``cellbench/weights.py`` (``y = x @ W +
b``, blocks stacked on a leading layer axis).  The only departures from
"as plain as possible" are for memory, not arithmetic: the blocks run
under ``lax.scan`` with ``jax.checkpoint`` around each, and training
walks the batch in blocks of rows, summing gradients.

``quant`` puts the reference in the program's place at a lower
precision, which is the control that ``correct`` has to reject: every
matrix multiplication's inputs (activations and weights) are rounded to
``"bfloat16"`` or, scaled per tensor, to ``"float8_e4m3fn"``.  The
rounding is in the forward pass only and gradients pass straight
through it, as a low-precision training recipe would have it: a
cotangent pushed through a cast to float8 underflows to nothing.
"""

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _quantizer(quant: Optional[str]):
    if quant is None:
        return lambda x: x
    if quant == "bfloat16":
        rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "float8_e4m3fn":
        def rounded(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
            y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return y * scale
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return lambda x: x + jax.lax.stop_gradient(rounded(x) - x)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps, q):
    B, S, H = x.shape
    hd = H // n_head
    mm = lambda a, w: jnp.matmul(q(a), q(w))
    h = _layer_norm(x, p["ln_1.g"], p["ln_1.b"], eps)
    split = lambda t: t.reshape(B, S, n_head, hd).transpose(0, 2, 1, 3)
    qh = split(mm(h, p["attn.wq"]) + p["attn.bq"])
    kh = split(mm(h, p["attn.wk"]) + p["attn.bk"])
    vh = split(mm(h, p["attn.wv"]) + p["attn.bv"])
    scores = jnp.einsum("bnsh,bnth->bnst", q(qh), q(kh)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnst,bnth->bnsh", q(probs), q(vh))
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H)
    x = x + mm(ctx, p["attn.wo"]) + p["attn.bo"]
    h = _layer_norm(x, p["ln_2.g"], p["ln_2.b"], eps)
    h = _gelu_new(mm(h, p["mlp.w_fc"]) + p["mlp.b_fc"])
    return x + mm(h, p["mlp.w_proj"]) + p["mlp.b_proj"]


def hidden_states(params: Dict, tokens, n_head: int, eps: float = 1e-5,
                  quant: Optional[str] = None):
    """tokens (B, S) int32 -> final-LayerNorm hidden states (B, S, H)."""
    q = _quantizer(quant)
    S = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:S][None]
    block = jax.checkpoint(partial(_block, n_head=n_head, eps=eps, q=q))
    x, _ = jax.lax.scan(lambda c, p: (block(c, p), None), x,
                        params["blocks"])
    return _layer_norm(x, params["ln_f.g"], params["ln_f.b"], eps)


def logits_at(params: Dict, tokens, positions, n_head: int,
              eps: float = 1e-5, quant: Optional[str] = None):
    """Full-forward logits (len(positions), V) of ONE sequence ``tokens``
    (S,) at the given positions: what the next token after each of them
    is drawn from."""
    q = _quantizer(quant)
    h = hidden_states(params, tokens[None], n_head, eps, quant)[0]
    return jnp.matmul(q(h[positions]), q(params["wte"]).T)


def loss(params: Dict, tokens, targets, n_head: int, eps: float = 1e-5,
         quant: Optional[str] = None):
    """Mean cross-entropy over every position of (B, S) ``tokens``
    against ``targets``."""
    q = _quantizer(quant)
    h = hidden_states(params, tokens, n_head, eps, quant)
    logits = jnp.matmul(q(h), q(params["wte"]).T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grads(params, tokens, targets, n_head, eps=1e-5, quant=None,
                   rows_per_block=2):
    """Loss and gradients over the whole batch, walked in blocks of
    rows (every block has as many rows, so the mean of block means is
    the batch mean)."""
    B = tokens.shape[0]
    if B % rows_per_block:
        raise ValueError(f"batch {B} is not a multiple of {rows_per_block}")
    n = B // rows_per_block
    tok = tokens.reshape(n, rows_per_block, -1)
    tgt = targets.reshape(n, rows_per_block, -1)
    vg = jax.value_and_grad(
        lambda p, a, b: loss(p, a, b, n_head, eps, quant))

    def body(carry, ab):
        l, g = vg(params, *ab)
        return (carry[0] + l / n,
                jax.tree.map(lambda s, x: s + x / n, carry[1], g)), None

    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(body, zero, (tok, tgt))
    return l, g


def adamw_step(params, grads, m, v, step, *, lr, beta1, beta2, eps,
               weight_decay):
    """One AdamW update with bias correction; ``step`` counts from 1."""
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
    v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + weight_decay * p),
        params, m, v)
    return params, m, v


def train_steps(params, batches, n_head, *, lr, beta1, beta2, eps_adam,
                weight_decay, ln_eps=1e-5, quant=None, rows_per_block=2,
                other_first_grad=None, keep_first_grad=False):
    """Follow the trainer through ``len(batches)`` steps of AdamW from
    ``params``.  ``batches`` is a list of (tokens, targets).  Returns a
    dict: the loss of each step, per leaf the norm of the first step's
    gradient, the parameters after the last step and, where
    ``other_first_grad`` (a tree like ``params``) is given, per leaf the
    norm of its difference from this run's first gradient;
    ``keep_first_grad`` also returns that gradient."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))

    def one_step(p, m, v, tok, tgt, i, other):
        l, g = loss_and_grads(p, tok, tgt, n_head, ln_eps, quant,
                              rows_per_block)
        gnorm = jax.tree.map(norm, g)
        diff = (None if other is None
                else jax.tree.map(lambda a, b: norm(a - b), g, other))
        p, m, v = adamw_step(p, g, m, v, i, lr=lr, beta1=beta1, beta2=beta2,
                             eps=eps_adam, weight_decay=weight_decay)
        return p, m, v, l, gnorm, diff, (g if keep_first_grad else None)

    with jax.default_matmul_precision("highest"):
        first = jax.jit(one_step, donate_argnums=(0, 1, 2))
        later = jax.jit(lambda p, m, v, t, y, i: one_step(
            p, m, v, t, y, i, None)[:4], donate_argnums=(0, 1, 2))
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        out = {"losses": []}
        for i, (tok, tgt) in enumerate(batches, start=1):
            if i == 1:
                (params, m, v, l, out["first_grad_norms"],
                 out["first_grad_diff_norms"], out["first_grad"]) = first(
                    params, m, v, tok, tgt, jnp.float32(i), other_first_grad)
            else:
                params, m, v, l = later(params, m, v, tok, tgt,
                                        jnp.float32(i))
            out["losses"].append(l)
    out["params"] = params
    return out
