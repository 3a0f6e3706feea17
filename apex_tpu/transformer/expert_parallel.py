"""Expert parallelism: mixture-of-experts FFN over a mesh axis.

**Beyond the reference**: apex has no MoE/expert parallelism (SURVEY
§2.4 "EP: No").  TPU-native design, GShard/Switch style:

- top-k router with capacity-factor token dropping — everything static
  shapes, so the whole layer jits: dispatch/combine are one-hot einsum
  tensors, never data-dependent gathers;
- experts sharded over a mesh axis (``ep_axis``, usually the ``dp``
  axis — "expert parallelism rides data parallelism"): tokens travel to
  their expert's device and back with two ``jax.lax.all_to_all`` over
  ICI, compute runs as batched per-expert matmuls on the MXU;
- auxiliary load-balancing loss (Switch Transformer eq. 4).

Expert weights are *sharded, not replicated*, over ``ep_axis``: each
device computes full gradients for its own experts (the all-to-all
brings every token routed to them), so data-parallel gradient sync must
SKIP expert parameters — :func:`is_expert_param` tells the train step
which ones.

**A second layer, for serving a share of a wide expert group**
(:func:`held_experts_ffn`, with its router :func:`route_group_limited`):
the layer is TOLD which experts it holds (``held``, a static range of
ids), routes over ALL of them — sigmoid scores, a bias that corrects
the choice but not the weight, group-limited top-k, weights
renormalised over the chosen and scaled — and computes the part of the
result its own experts give, dropping nothing: the live assignments
are sorted by expert and each projection is ONE grouped matmul (the
Pallas grouped GEMM that ships with JAX on a TPU, ``jax.lax.ragged_dot``
elsewhere: :func:`_grouped_matmul`) over static buffers sized for the
worst case, every assignment held.  With ``held`` = all experts it is the whole layer; what absent
experts would add is simply not there (no exchange, no stand-in).
:func:`moe_ffn` above stays the training layer (capacity factor,
dropping, ``all_to_all``); this one has no backward-tuned path and no
exchange yet (ROADMAP, Queue 2).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def moe_init(key, hidden_size: int, ffn_size: int, num_experts: int,
             layers: Optional[int] = None, std: float = 0.02):
    """Router + expert FFN params.  With ``layers``, adds a leading L dim
    (for scan-over-layers models)."""
    k = jax.random.split(key, 3)
    ld = () if layers is None else (layers,)
    init = lambda kk, *s: jax.random.normal(kk, ld + s, jnp.float32) * std
    return {
        "router": init(k[0], hidden_size, num_experts),
        "w1": init(k[1], num_experts, ffn_size, hidden_size),
        "b1": jnp.zeros(ld + (num_experts, ffn_size)),
        "w2": init(k[2], num_experts, hidden_size, ffn_size) / np.sqrt(2.0),
        "b2": jnp.zeros(ld + (num_experts, hidden_size)),
    }


EXPERT_PARAM_KEYS = ("w1", "b1", "w2", "b2")


def is_expert_param(path_keys) -> bool:
    """True for params sharded over the expert axis (their grads are
    device-local and must not be averaged over dp)."""
    names = [getattr(p, "key", getattr(p, "name", None)) for p in path_keys]
    return any(n in EXPERT_PARAM_KEYS for n in names) and any(
        n in ("moe", "experts") for n in names
    )


def _top_k_mask(probs, top_k: int, capacity: int):
    """Static-shape top-k dispatch with capacity dropping.

    probs: (T, E) f32.  Returns (dispatch (T, E, C) one-hot,
    combine (T, E, C) gate-weighted, aux-loss ingredients).
    Slot priority is GShard's: all slot-0 assignments claim capacity
    before any slot-1 assignment."""
    T, E = probs.shape
    masks = []
    p = probs
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        m = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        masks.append(m)
        p = p * (1.0 - m)  # knock out the chosen expert for the next slot

    # capacity accounting, slot-major: (K*T, E) running count per expert
    stacked = jnp.concatenate(masks, axis=0)  # (K*T, E)
    pos = jnp.cumsum(stacked, axis=0) - stacked  # tokens ahead of me
    keep = (pos < capacity).astype(probs.dtype) * stacked
    loc = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=probs.dtype)

    dispatch = (keep[..., None] * loc).reshape(len(masks), T, E, capacity).sum(0)
    gate = (probs[None] * jnp.stack(masks)).sum(0)  # (T, E) chosen probs
    if top_k == 1:
        # Switch Transformer: weight by the raw router prob — the output
        # path is what carries the router gradient for top-1
        weights = gate
    else:
        # GShard: renormalize over the chosen experts
        weights = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    combine = dispatch * weights[..., None]
    return dispatch, combine, masks[0]


def load_balancing_loss(probs, mask1):
    """Switch Transformer aux loss: E · Σ_e f_e · P_e (eq. 4)."""
    E = probs.shape[-1]
    f = jnp.mean(mask1, axis=0)  # fraction of tokens per expert (top-1)
    P = jnp.mean(probs, axis=0)  # mean router prob per expert
    return E * jnp.sum(f * P)


def moe_ffn(
    x,
    params,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    ep_axis: Optional[str] = None,
    activation=partial(jax.nn.gelu, approximate=True),
):
    """MoE FFN.  x: (..., H) — leading dims are flattened to tokens.

    With ``ep_axis`` (inside shard_map): ``params`` hold the LOCAL
    expert shard (E_local = E/ep on the expert dim) and tokens exchange
    over the axis with all_to_all.  Without: dense (all experts local).

    Returns (out, aux_loss).
    """
    orig_shape = x.shape
    H = orig_shape[-1]
    xf = x.reshape(-1, H)
    T = xf.shape[0]

    ep = 1 if ep_axis is None else jax.lax.axis_size(ep_axis)
    E_local = params["w1"].shape[0]
    E = E_local * ep

    logits = jnp.matmul(xf.astype(jnp.float32), params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)

    capacity = max(1, int(np.ceil(top_k * capacity_factor * T / E)))
    dispatch, combine, mask1 = _top_k_mask(probs, top_k, capacity)
    aux = load_balancing_loss(probs, mask1)

    cd = x.dtype
    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(cd), xf)  # (E, C, H)

    if ep_axis is not None:
        # (E, C, H) -> (E_local, ep·C, H): expert-major blocks scatter to
        # their owners, received capacity blocks stack source-major
        expert_in = jax.lax.all_to_all(
            expert_in, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )

    h = jnp.einsum("ech,efh->ecf", expert_in, params["w1"].astype(cd))
    h = activation(h + params["b1"].astype(cd)[:, None, :])
    y = jnp.einsum("ecf,ehf->ech", h, params["w2"].astype(cd))
    y = y + params["b2"].astype(cd)[:, None, :]

    if ep_axis is not None:
        # (E_local, ep·C, H) -> (E, C, H): the exact transpose of the way in
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0, tiled=True)

    out = jnp.einsum("tec,ech->th", combine.astype(cd), y)
    return out.reshape(orig_shape), aux.astype(jnp.float32)


def moe_param_specs(ep_axis: Optional[str] = "dp", layers: bool = True):
    """PartitionSpecs for :func:`moe_init` params: experts sharded over
    ``ep_axis`` (None = replicated), router replicated."""
    from jax.sharding import PartitionSpec as P

    ld = (None,) if layers else ()
    return {
        "router": P(*ld, None, None),
        "w1": P(*ld, ep_axis, None, None),
        "b1": P(*ld, ep_axis, None),
        "w2": P(*ld, ep_axis, None, None),
        "b2": P(*ld, ep_axis, None),
    }


# ------------------------------------------------ held experts (serving)
def route_group_limited(x, router_w, bias, *, top_k: int, n_group: int,
                        topk_group: int, scale: float):
    """Bias-corrected, group-limited top-k routing over ALL experts.

    ``x``: (T, H); ``router_w``: (H, E); ``bias``: (E,) — added to the
    scores for the CHOICE only.  In float32 whatever the inputs' dtype:
    ``s = sigmoid(x W)``; the ``n_group`` groups of ``E / n_group``
    consecutive experts are each scored by the sum of their 2 best
    ``s + bias``, the best ``topk_group`` groups stay, and the
    ``top_k`` best ``s + bias`` among them pick the experts.  Their
    weights are the ORIGINAL ``s``, divided by their sum and multiplied
    by ``scale``.  Returns ``(ids (T, top_k) int32, weights (T, top_k)
    float32)``.
    """
    T = x.shape[0]
    E = router_w.shape[1]
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32)))
    choice = s + bias.astype(jnp.float32)[None]
    per_group = choice.reshape(T, n_group, E // n_group)
    group_score = jax.lax.top_k(per_group, 2)[0].sum(-1)       # (T, G)
    keep = jax.lax.top_k(group_score, topk_group)[1]           # (T, tg)
    group_ok = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(group_ok, E // n_group, axis=1),
                       choice, -jnp.inf)
    ids = jax.lax.top_k(masked, top_k)[1].astype(jnp.int32)    # (T, k)
    picked = jnp.take_along_axis(s, ids, axis=1, mode="clip")  # top_k's
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    return ids, weights


#: (rows, contraction, columns) tile of the Pallas grouped matmul: 88%
#: of the HBM roofline at 64 live rows over 16 experts of 7168 x 2048 on
#: a v5e, where XLA's own ``ragged_dot`` kernel reached 40% (PERF.md,
#: PR 26)
GROUPED_TILING = (128, 1024, 1024)


def _grouped_matmul(rows, w, group_sizes, impl):
    """``rows[sizes[:g].sum() : sizes[:g+1].sum()] @ w[g]`` for every
    group ``g``: ``jax.lax.ragged_dot`` ("xla"), or the Pallas grouped
    GEMM that ships with JAX (megablox ``gmm``; "pallas", "interpret",
    and "auto" on a TPU), which visits only the row tiles that hold a
    live row.  Rows past the live ones come out undefined."""
    from apex_tpu.utils.platform import on_tpu

    if impl not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(f"impl must be 'auto', 'pallas', 'interpret' or "
                         f"'xla'; got {impl!r}")
    tm = next((t for t in (GROUPED_TILING[0], 64, 32, 16, 8)
               if rows.shape[0] % t == 0), None)
    if impl == "xla" or (impl == "auto" and not on_tpu()) or tm is None:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    _, tk, tn = GROUPED_TILING
    return gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
               tiling=(tm, min(tk, w.shape[1]), min(tn, w.shape[2])),
               interpret=(impl == "interpret"))


def grouped_gated_ffn(rows, w_gate, w_up, w_down, group_sizes,
                      impl="auto"):
    """The gated-SiLU FFN of ``G`` experts over rows sorted by expert:
    ``rows`` (M, H), the first ``group_sizes[0]`` of them expert 0's
    and so on; ``w_gate``/``w_up``: (G, H, F); ``w_down``: (G, F, H).
    One grouped matmul a projection (:func:`_grouped_matmul`); rows
    past ``sum(group_sizes)`` come out UNDEFINED (the caller masks
    them).  An expert with no row costs nothing: its weights are not
    read."""
    gate = _grouped_matmul(rows, w_gate, group_sizes, impl)
    up = _grouped_matmul(rows, w_up, group_sizes, impl)
    return _grouped_matmul(jax.nn.silu(gate) * up, w_down, group_sizes,
                           impl)


def held_experts_ffn(x, params, held: range, *, top_k: int, n_group: int,
                     topk_group: int, scale: float, token_mask=None,
                     layer=None, impl="auto"):
    """The routed part of an expert layer that the experts ``held``
    give, for every token, with no assignment dropped.

    ``x``: (T, H).  ``params``: ``router`` (H, E) and ``router_bias``
    (E,) over ALL ``E`` experts; ``we_gate``/``we_up`` (n_held, H, F)
    and ``we_down`` (n_held, F, H), the held experts' weights in id
    order.  ``held``: the static ``range`` of ids held
    (``range(E)``: the whole layer).  ``token_mask``: (T,) bool —
    tokens that are padding or an empty slot route nowhere.

    Each token's ``top_k`` assignments are chosen over all experts and
    weighted over all ``top_k`` (:func:`route_group_limited`); those to
    a held expert are sorted by expert and run through
    :func:`grouped_gated_ffn` in a static buffer of ``T * top_k`` rows,
    the worst case.  Returns ``(out (T, H), counts)`` with ``counts``
    the int32 scalars ``assignments_held`` (assignments computed
    here), ``assignments_all`` (``top_k`` a live token) and
    ``experts_hit`` (held experts with at least one).
    """
    T, H = x.shape
    n_held = len(held)
    experts = {k: params[k] for k in ("we_gate", "we_up", "we_down")}
    if layer is None:
        experts = {k: w[None] for k, w in experts.items()}
        layer = 0
    n_layers = experts["we_gate"].shape[0]
    if held.step != 1 or experts["we_gate"].shape[1] != n_held:
        raise ValueError(
            f"held {held} must be a contiguous range matching the "
            f"{experts['we_gate'].shape[1]} experts' weights given")
    ids, weights = route_group_limited(
        x, params["router"], params["router_bias"], top_k=top_k,
        n_group=n_group, topk_group=topk_group, scale=scale)
    live = (ids >= held.start) & (ids < held.stop)
    if token_mask is not None:
        live = live & token_mask[:, None]
    A = T * top_k
    local = jnp.where(live, ids - held.start, n_held).reshape(A)
    order = jnp.argsort(local, stable=True)           # held first, by expert
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None],
        axis=0, dtype=jnp.int32)
    rows = jnp.take(x, order // top_k, axis=0)        # (A, H)
    # the layers' experts side by side as groups; only this layer's
    # have rows
    all_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * n_held,), jnp.int32), group_sizes,
        (jnp.asarray(layer, jnp.int32) * n_held,))
    flat = {k: w.reshape((n_layers * n_held,) + w.shape[2:])
            for k, w in experts.items()}
    y = grouped_gated_ffn(rows, flat["we_gate"], flat["we_up"],
                          flat["we_down"], all_sizes, impl=impl)
    # a row past the live ones belongs to no group: whatever the grouped
    # matmul left there is replaced, not multiplied away
    w = jnp.take(jnp.where(live, weights, 0.0).reshape(A), order)
    y = jnp.where(w[:, None] > 0, y.astype(jnp.float32) * w[:, None], 0.0)
    # back to assignment order, then each token sums its own
    back = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32))
    out = jnp.take(y, back, axis=0).reshape(T, top_k, H).sum(1)
    n_tokens = T if token_mask is None else jnp.sum(token_mask)
    counts = {
        "assignments_held": jnp.sum(group_sizes),
        "assignments_all": jnp.asarray(n_tokens * top_k, jnp.int32),
        "experts_hit": jnp.sum(group_sizes > 0, dtype=jnp.int32),
    }
    return out.astype(x.dtype), counts
