"""float64 NumPy oracles for the five fused optimizers.

Written from the reference's formulas (``csrc/multi_tensor_adam.cu``,
``multi_tensor_sgd_kernel.cu``, ``multi_tensor_lamb.cu``,
``multi_tensor_novograd.cu``, ``multi_tensor_adagrad.cu`` and their
drivers under ``apex/optimizers``), with the reference's defaults, and
importing nothing from ``apex_tpu.optimizers``: a test that compares an
optimizer with :func:`run` pins its arithmetic, not its layout.

One oracle step takes the leaves of a tree in ``tree_flatten`` order as
float64 arrays.  ``leaf_hypers`` is one dict a leaf with the overrides a
param group gives it (``lr`` absolute, ``lr_scale``, ``weight_decay``,
``momentum``, ``use_trust_ratio``).
"""

import jax
import ml_dtypes
import numpy as np


def clip_grads(gs, max_norm):
    """torch ``clip_grad_norm_``: every gradient times
    ``min(max_norm / (total_norm + 1e-6), 1)``."""
    total = np.sqrt(sum(float((g * g).sum()) for g in gs))
    coef = min(max_norm / (total + 1e-6), 1.0)
    return [g * coef for g in gs]


def _lr(h, lr):
    return h["lr"] if "lr" in h else lr * h.get("lr_scale", 1.0)


def adam_step(ps, gs, st, t, hs, *, lr=1e-3, bias_correction=True,
              betas=(0.9, 0.999), eps=1e-8, adam_w_mode=True,
              weight_decay=0.0):
    b1, b2 = betas
    bc1 = 1 - b1 ** t if bias_correction else 1.0
    bc2 = 1 - b2 ** t if bias_correction else 1.0
    m, v = (st.setdefault(k, [np.zeros_like(p) for p in ps])
            for k in ("exp_avg", "exp_avg_sq"))
    out = []
    for i, (p, g, h) in enumerate(zip(ps, gs, hs)):
        wd = h.get("weight_decay", weight_decay)
        if not adam_w_mode:
            g = g + wd * p
        m[i] = b1 * m[i] + (1 - b1) * g
        v[i] = b2 * v[i] + (1 - b2) * g * g
        update = (m[i] / bc1) / (np.sqrt(v[i] / bc2) + eps)
        if adam_w_mode:
            update = update + wd * p
        out.append(p - _lr(h, lr) * update)
    return out


def sgd_step(ps, gs, st, t, hs, *, lr, momentum=0.0, dampening=0.0,
             weight_decay=0.0, nesterov=False, wd_after_momentum=False):
    buf = st.setdefault("momentum_buffer", [np.zeros_like(p) for p in ps])
    out = []
    for i, (p, g, h) in enumerate(zip(ps, gs, hs)):
        wd = h.get("weight_decay", weight_decay)
        mu = h.get("momentum", momentum)
        if not wd_after_momentum:
            g = g + wd * p
        if mu != 0:
            buf[i] = g if t == 1 else mu * buf[i] + (1 - dampening) * g
            g = g + mu * buf[i] if nesterov else buf[i]
        if wd_after_momentum:
            g = g + wd * p
        out.append(p - _lr(h, lr) * g)
    return out


def lamb_step(ps, gs, st, t, hs, *, lr=1e-3, bias_correction=True,
              betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01,
              adam_w_mode=True, grad_averaging=True, max_grad_norm=1.0,
              use_nvlamb=False):
    b1, b2 = betas
    b3 = 1 - b1 if grad_averaging else 1.0
    bc1 = 1 - b1 ** t if bias_correction else 1.0
    bc2 = 1 - b2 ** t if bias_correction else 1.0
    m, v = (st.setdefault(k, [np.zeros_like(p) for p in ps])
            for k in ("exp_avg", "exp_avg_sq"))
    gn = np.sqrt(sum(float((g * g).sum()) for g in gs))
    clip = gn / max_grad_norm if gn > max_grad_norm else 1.0
    out = []
    for i, (p, g, h) in enumerate(zip(ps, gs, hs)):
        wd = h.get("weight_decay", weight_decay)
        g = g / clip
        if not adam_w_mode:
            g = g + wd * p
        m[i] = b1 * m[i] + b3 * g
        v[i] = b2 * v[i] + (1 - b2) * g * g
        u = (m[i] / bc1) / (np.sqrt(v[i] / bc2) + eps)
        if adam_w_mode:
            u = u + wd * p
        ratio = _lr(h, lr)
        if h.get("use_trust_ratio", True) and (use_nvlamb or wd != 0):
            pn, un = np.sqrt((p * p).sum()), np.sqrt((u * u).sum())
            if pn != 0 and un != 0:
                ratio = ratio * (pn / un)
        out.append(p - ratio * u)
    return out


def novograd_step(ps, gs, st, t, hs, *, lr=1e-3, bias_correction=True,
                  betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                  reg_inside_moment=False, grad_averaging=True,
                  norm_type=2, init_zero=False):
    b1, b2 = betas
    b3 = 1 - b1 if grad_averaging else 1.0
    bc1 = 1 - b1 ** t if bias_correction else 1.0
    bc2 = np.sqrt(1 - b2 ** t) if bias_correction else 1.0
    m = st.setdefault("exp_avg", [np.zeros_like(p) for p in ps])
    gn = st.setdefault("exp_avg_sq", [None] * len(ps))
    out = []
    for i, (p, g) in enumerate(zip(ps, gs)):
        fresh = np.sqrt((g * g).sum()) if norm_type == 2 else np.abs(g).max()
        if gn[i] is None:
            # the first blend is a no-op unless the norm starts at zero
            gn[i] = 0.0 if init_zero else fresh
        if norm_type == 2:
            gn[i] = np.sqrt(b2 * gn[i] ** 2 + (1 - b2) * fresh ** 2)
        else:
            gn[i] = b2 * gn[i] + (1 - b2) * fresh
        denom = gn[i] / bc2 + eps
        if reg_inside_moment:
            m[i] = b1 * m[i] + b3 * (g / denom + weight_decay * p)
            out.append(p - lr * (m[i] / bc1))
        else:
            m[i] = b1 * m[i] + b3 * g
            out.append(p - lr * ((m[i] / bc1) / denom + weight_decay * p))
    return out


def adagrad_step(ps, gs, st, t, hs, *, lr=1e-2, eps=1e-10, weight_decay=0.0,
                 adagrad_w_mode=False):
    acc = st.setdefault("sum", [np.zeros_like(p) for p in ps])
    out = []
    for i, (p, g, h) in enumerate(zip(ps, gs, hs)):
        wd = h.get("weight_decay", weight_decay)
        if not adagrad_w_mode:
            g = g + wd * p
        acc[i] = acc[i] + g * g
        update = g / (np.sqrt(acc[i]) + eps)
        if adagrad_w_mode:
            update = update + wd * p
        out.append(p - _lr(h, lr) * update)
    return out


STEPS = {"adam": adam_step, "sgd": sgd_step, "lamb": lamb_step,
         "novograd": novograd_step, "adagrad": adagrad_step}


def _f64(x):
    return np.asarray(x).astype(np.float64)


def _storage(x):
    dt = np.asarray(x).dtype
    return ml_dtypes.bfloat16 if dt.name == "bfloat16" else dt


def run(name, params, grads_seq, *, clip_norm=None, master_weights=False,
        leaf_hypers=None, **hp):
    """``len(grads_seq)`` steps of optimizer ``name`` from ``params``.

    Parameters are kept as their storage dtype holds them: rounded to it
    after every step, or, with ``master_weights``, carried unrounded
    (the master) and rounded once at the end.  Returns a dict of
    float64 leaf lists: ``params``, ``master`` (None without one) and
    the optimizer's state slots under the reference's names."""
    leaves = jax.tree.leaves(params)
    dtypes = [_storage(x) for x in leaves]
    ps = [_f64(x) for x in leaves]
    hs = leaf_hypers or [{}] * len(ps)
    st = {}
    for t, grads in enumerate(grads_seq, start=1):
        gs = [_f64(g) for g in jax.tree.leaves(grads)]
        if clip_norm is not None:
            gs = clip_grads(gs, clip_norm)
        ps = STEPS[name](ps, gs, st, t, hs, **hp)
        if not master_weights:
            ps = [_f64(p.astype(dt)) for p, dt in zip(ps, dtypes)]
    out = dict(st, params=ps, master=None)
    if master_weights:
        out.update(master=ps,
                   params=[_f64(p.astype(dt)) for p, dt in zip(ps, dtypes)])
    return out


#: a value that went through a bfloat16 rounding may land on the
#: neighbouring bfloat16 where float32 and float64 arithmetic fall on
#: either side of a tie: one part in 2**7, and whatever read it after
BF16_BAND = dict(rtol=2.0 ** -7, atol=1e-6)


def stepped(opt, params, grads_seq, **kw):
    """``len(grads_seq)`` updates from ``opt.init(params)``: the
    optimizer's side of a comparison with :func:`run`."""
    state = opt.init(params)
    for grads in grads_seq:
        params, state = opt.update(grads, state, params, **kw)
    return params, state


def assert_matches_oracle(got, want, loose=(), rtol=1e-5, atol=1e-6,
                          err=""):
    """``got`` (a tree) against ``want`` (the oracle's float64 leaves in
    flatten order) within ``rtol``/``atol``; the leaves flagged in
    ``loose`` within :data:`BF16_BAND`."""
    got = jax.tree.leaves(got)
    assert len(got) == len(want), err
    loose = loose or [False] * len(got)
    for i, (x, y) in enumerate(zip(got, want)):
        band = BF16_BAND if loose[i] else dict(rtol=rtol, atol=atol)
        np.testing.assert_allclose(
            np.asarray(x, np.float64), np.asarray(y, np.float64),
            err_msg=f"{err} (leaf {i})", **band)
