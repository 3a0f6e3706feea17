"""The plain reference for the ``afmoe`` block (Arcee Trinity), as a
TRAINING reference: forward pass, cross-entropy loss, gradients by
``jax.grad``, AdamW and the routers' balance rule, in straightforward
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
No kernels, no sort, no buffers.  It imports nothing of ``apex_tpu``.

The model, with ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g`` and no
bias anywhere:

- ``x = E[tokens] * sqrt(H)``;
- a layer: ``a = Attn(RMS(x; g1))``, ``x = x + RMS(a; g2)``,
  ``m = FFN(RMS(x; g3))``, ``x = x + RMS(m; g4)``;
- ``Attn(h)``: ``q = h Wq`` as (n, d), ``k = h Wk``, ``v = h Wv`` as
  (kv, d), ``gate = h Wg``; ``q`` and ``k`` RMS-normed over the head
  dimension (one gain vector of ``d`` each); a ``sliding_attention``
  layer rotates ``q`` and ``k`` (theta ``rope_theta``, the whole head,
  ``rotate_half`` pairing) and key ``j`` is visible to query ``i`` iff
  ``0 <= i - j < sliding_window``; a ``full_attention`` layer rotates
  nothing and ``j <= i``; a DENSE mask, scores scaled by ``d^-1/2``,
  query head ``h`` reads key/value head ``h // (n / kv)``;
  ``Attn = (softmax(q k^T) v * sigmoid(gate)) Wo``;
- the leading ``num_dense_layers``: ``Wdown(silu(Wgate h) * Wup h)``;
- an expert layer: ``s = sigmoid(h Wr)``; the ``num_experts_per_tok``
  experts with the largest ``s + b`` are chosen (``b`` the expert bias:
  choice only, a constant to the gradient); ``w = s[chosen] / (sum
  s[chosen] + 1e-20) * route_scale``; ``FFN = Shared(h) + sum_e w_e
  Expert_e(h)`` over the experts HELD, every held expert run on every
  token and weighted by its routing weight or by zero;
- ``loss = mean CE(RMS(x_L; gf) Whead, targets)``; no auxiliary loss;
- after every optimizer step, in every expert layer, with ``c_e`` the
  assignments expert ``e`` of ALL the router's experts got:
  ``b_e <- b_e + load_balance_coeff * sign(mean(c) - c_e)``.

Weights come in the layout of ``cellbench/weights_afmoe.py``: a flat
dict under the published module names, matrices ``(out, in)`` as
``nn.Linear`` stores them, the held experts of a layer stacked on a
leading axis.

Departures from "as plain as possible", all for memory and none for
arithmetic (at the cell's size the float32 parameters, gradient and two
moments are 11.3 GB of the chip's 15.75): rows go one sequence at a
time, the gradient summed over them; attention goes one query head at
a time and the held experts one at a time (``lax.map`` / ``lax.scan``
with ``jax.checkpoint`` around the body), each layer is one
``jax.checkpoint``; the cross entropy goes in blocks of positions;
a sequence's gradient is added into a donated accumulator inside the
program that computes it; Adam's moments are first allocated by the
first update and wait on the host between updates (one sequence's
gradient walk needs 12.3 GB with the parameters and the accumulator);
and a first gradient given for comparison stays on the host and is
compared leaf by leaf.

``quant`` puts the reference in the program's place at a lower
precision (the control that ``correct`` has to reject): every matrix
multiplication's inputs are rounded to ``"bfloat16"`` or, scaled per
tensor, ``"float8_e4m3fn"``, in the forward pass only (gradients pass
straight through).  The router's product stays float32, as the
configuration states it.
"""

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

CE_BLOCK = 2048     # positions a block of the cross entropy


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


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, theta):
    """Rotary embedding of (S, d) at positions 0..S-1, ``rotate_half``
    pairing: element ``i`` pairs with ``i + d/2``."""
    S, d = x.shape
    inv = theta ** (-np.arange(0, d // 2, dtype=np.float64) / (d // 2))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, p, pre, conf, kind, q):
    """``h`` (S, H), one sequence."""
    S = h.shape[0]
    n, kv, d = (conf["num_attention_heads"], conf["num_key_value_heads"],
                conf["head_dim"])
    eps = conf["rms_norm_eps"]
    mm = lambda a, w: jnp.matmul(q(a), q(w).T)
    heads = lambda t, nh: t.reshape(S, nh, d).transpose(1, 0, 2)
    qh = _rms(heads(mm(h, p[pre + "q_proj.weight"]), n),
              p[pre + "q_norm.weight"], eps)
    kh = _rms(heads(mm(h, p[pre + "k_proj.weight"]), kv),
              p[pre + "k_norm.weight"], eps)
    vh = heads(mm(h, p[pre + "v_proj.weight"]), kv)
    gap = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    visible = gap >= 0
    if kind == "sliding_attention":
        rot = partial(_rotate, theta=float(conf["rope_theta"]))
        qh, kh = jax.vmap(rot)(qh), jax.vmap(rot)(kh)
        visible = visible & (gap < conf["sliding_window"])

    @jax.checkpoint
    def one_head(args):
        qi, index = args
        ki, vi = kh[index // (n // kv)], vh[index // (n // kv)]
        scores = jnp.matmul(q(qi), q(ki).T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
        return jnp.matmul(q(probs), q(vi))

    o = jax.lax.map(one_head, (qh, jnp.arange(n)))          # (n, S, d)
    o = o.transpose(1, 0, 2).reshape(S, n * d)
    o = o * jax.nn.sigmoid(mm(h, p[pre + "gate_proj.weight"]))
    return mm(o, p[pre + "o_proj.weight"])


def _gated(h, w_gate, w_up, w_down, q):
    mm = lambda a, w: jnp.matmul(q(a), q(w).T)
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def route(h, router_w, bias, conf):
    """``(ids (S, k), weights (S, k))``."""
    s = jax.nn.sigmoid(jnp.matmul(h, router_w.T))
    ids = jax.lax.top_k(s + jax.lax.stop_gradient(bias)[None],
                        conf["num_experts_per_tok"])[1]
    picked = jnp.take_along_axis(s, ids, axis=1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * conf["route_scale"]
    return ids, w


def _expert_ffn(h, p, pre, bias, conf, held_start, q):
    """Shared expert + the held experts, each on EVERY token, weighted
    by the token's routing weight for it or by zero.  Returns ``(out,
    load (E,))``."""
    E = p[pre + "router.gate.weight"].shape[0]
    ids, w = route(h, p[pre + "router.gate.weight"], bias, conf)
    # (S, E): the routing weight of each expert for each token, or 0
    dense_w = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], ids].add(w)
    load = jnp.zeros((E,), jnp.int32).at[ids.reshape(-1)].add(1)
    n_held = p[pre + "experts.gate_proj.weight"].shape[0]

    @jax.checkpoint
    def one_expert(acc, xs):
        wg, wu, wd, col = xs
        return acc + _gated(h, wg, wu, wd, q) * col[:, None], None

    cols = dense_w[:, held_start:held_start + n_held].T     # (n_held, S)
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p[pre + "experts.gate_proj.weight"],
         p[pre + "experts.up_proj.weight"],
         p[pre + "experts.down_proj.weight"], cols))
    shared = _gated(h, p[pre + "shared_experts.gate_proj.weight"],
                    p[pre + "shared_experts.up_proj.weight"],
                    p[pre + "shared_experts.down_proj.weight"], q)
    return shared + routed, load


def hidden_states(params: Dict, biases, tokens, conf: Dict, held_start=0,
                  quant: Optional[str] = None):
    """One sequence ``tokens`` (S,) -> ``(final-normed states (S, H),
    loads (expert layers, E))``.  ``biases``: (expert layers, E)."""
    q = _quantizer(quant)
    eps = conf["rms_norm_eps"]
    x = params["model.embed_tokens.weight"][tokens] * math.sqrt(
        conf["hidden_size"])
    loads = []
    for i, kind in enumerate(conf["layer_types"]):
        pre = f"model.layers.{i}."
        dense = i < conf["num_dense_layers"]

        @jax.checkpoint
        def layer(x, p, bias, pre=pre, kind=kind, dense=dense):
            a = _attention(_rms(x, p[pre + "input_layernorm.weight"], eps),
                           p, pre + "self_attn.", conf, kind, q)
            x = x + _rms(a, p[pre + "post_attention_layernorm.weight"], eps)
            h = _rms(x, p[pre + "pre_mlp_layernorm.weight"], eps)
            if dense:
                m, load = _gated(h, p[pre + "mlp.gate_proj.weight"],
                                 p[pre + "mlp.up_proj.weight"],
                                 p[pre + "mlp.down_proj.weight"], q), None
            else:
                m, load = _expert_ffn(h, p, pre + "mlp.", bias, conf,
                                      held_start, q)
            return x + _rms(m, p[pre + "post_mlp_layernorm.weight"],
                            eps), load

        mine = {k: v for k, v in params.items() if k.startswith(pre)}
        x, load = layer(x, mine, None if dense else
                        biases[i - conf["num_dense_layers"]])
        if load is not None:
            loads.append(load)
    return _rms(x, params["model.norm.weight"], eps), jnp.stack(loads)


def sequence_loss(params, biases, tokens, targets, conf, held_start=0,
                  quant=None):
    """SUM of the cross entropy over one sequence's positions, in
    blocks of positions, and the loads."""
    q = _quantizer(quant)
    h, loads = hidden_states(params, biases, tokens, conf, held_start, quant)
    S = h.shape[0]
    block = math.gcd(S, CE_BLOCK)

    @jax.checkpoint
    def one_block(total, xs):
        hb, tb = xs
        logits = jnp.matmul(q(hb), q(params["lm_head.weight"]).T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return total - jnp.sum(jnp.take_along_axis(
            logp, tb[:, None], axis=-1)), None

    total, _ = jax.lax.scan(
        one_block, jnp.float32(0.0),
        (h.reshape(S // block, block, -1), targets.reshape(S // block, block)))
    return total, loads


def grad_function(conf, held_start=0, quant=None):
    """The jitted step :func:`loss_and_grads` walks a batch with (built
    once a run: a new closure would compile again): one sequence's loss
    and gradient, the gradient ADDED into a donated accumulator inside
    the same program, so that no second gradient-sized buffer lives
    beside it."""
    def accumulate(params, biases, tokens, targets, acc, scale):
        (total, loads), g = jax.value_and_grad(
            lambda p: sequence_loss(p, biases, tokens, targets, conf,
                                    held_start, quant), has_aux=True)(params)
        return (total * scale, loads,
                jax.tree.map(lambda a, x: a + x * scale, acc, g))

    return jax.jit(accumulate, donate_argnums=4)


def loss_and_grads(params, biases, tokens, targets, accumulate):
    """Mean loss over (B, S) ``tokens``, its gradient, and the loads
    (expert layers, E) of the whole batch: one sequence at a time.
    ``accumulate``: :func:`grad_function`."""
    B, S = tokens.shape
    scale = jnp.float32(1.0 / (B * S))
    grads = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
    total, loads = 0.0, 0
    for b in range(B):
        l, ld, grads = accumulate(params, biases, tokens[b], targets[b],
                                  grads, scale)
        total, loads = total + l, loads + ld
    return total, grads, loads


def adamw_step(params, grads, m, v, step, *, lr, beta1, beta2, eps,
               weight_decay):
    """One AdamW update with bias correction; ``step`` counts from 1.
    Weight decay on matrices, none on gains (one-dimensional leaves).
    ``m``/``v`` None: the first step, which allocates them."""
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    if m is None:
        m = jax.tree.map(lambda g: (1 - beta1) * g, grads)
        v = jax.tree.map(lambda g: (1 - beta2) * g * g, grads)
    else:
        m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
        v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g, v,
                         grads)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (
            (a / bc1) / (jnp.sqrt(b / bc2) + eps)
            + (weight_decay if p.ndim > 1 else 0.0) * p),
        params, m, v)
    return params, m, v


def balance_update(biases, loads, coeff):
    """``b_e <- b_e + coeff * sign(mean(c) - c_e)``, every layer."""
    c = loads.astype(jnp.float32)
    return biases + coeff * jnp.sign(jnp.mean(c, -1, keepdims=True) - c)


def train_steps(params, batches, conf, *, lr, beta1, beta2, eps_adam,
                weight_decay, held_start=0, quant=None,
                other_first_grad=None, keep_first_grad=False):
    """Follow the trainer through ``len(batches)`` steps from ``params``
    and zero biases.  ``batches``: a list of (tokens, targets).  Returns
    a dict: ``losses``; ``first_grad_norms`` per leaf; ``loads`` of each
    step (expert layers, E); ``biases`` after the last step; ``params``
    after it; where ``other_first_grad`` (a HOST tree like ``params``)
    is given, ``first_grad_diff_norms``, per leaf the norm of its
    difference from this run's first gradient (leaf by leaf: the two
    gradients are never on the device together); ``keep_first_grad``
    also returns that gradient, on the host."""
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))))
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    n_moe = conf["num_hidden_layers"] - conf["num_dense_layers"]
    hyper = dict(lr=lr, beta1=beta1, beta2=beta2, eps=eps_adam,
                 weight_decay=weight_decay)
    with jax.default_matmul_precision("highest"):
        first = jax.jit(lambda p, g, i: adamw_step(p, g, None, None, i,
                                                   **hyper),
                        donate_argnums=(0, 1))
        later = jax.jit(lambda p, g, m, v, i: adamw_step(p, g, m, v, i,
                                                         **hyper),
                        donate_argnums=(0, 1, 2, 3))
        router = (f"model.layers.{conf['num_dense_layers']}"
                  f".mlp.router.gate.weight")
        biases = jnp.zeros((n_moe, params[router].shape[0]), jnp.float32)
        accumulate = grad_function(conf, held_start, quant)
        m = v = None
        out = {"losses": [], "loads": [], "first_grad_diff_norms": None,
               "first_grad": None}
        for i, (tok, tgt) in enumerate(batches, start=1):
            l, g, loads = loss_and_grads(params, biases, tok, tgt,
                                         accumulate)
            if i == 1:
                out["first_grad_norms"] = {k: norm(x) for k, x in g.items()}
                if other_first_grad is not None:
                    out["first_grad_diff_norms"] = {
                        k: diff(x, jnp.asarray(other_first_grad[k]))
                        for k, x in g.items()}
                if keep_first_grad:
                    out["first_grad"] = jax.device_get(g)
                params, m, v = first(params, g, jnp.float32(i))
            else:
                params, m, v = later(params, g, jax.device_put(m),
                                     jax.device_put(v), jnp.float32(i))
            if i < len(batches):
                # the moments wait on the host for the next update: a
                # step's gradient walk needs the room they take
                m, v = jax.device_get(m), jax.device_get(v)
            biases = balance_update(biases, loads, conf["load_balance_coeff"])
            out["losses"].append(l)
            out["loads"].append(loads)
    out["params"], out["biases"] = params, biases
    return out
