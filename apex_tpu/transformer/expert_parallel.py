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

**The same layer trains** (``buffer_rows=``): the sort compacts the
held assignments first, and the grouped matmuls walk them in static
chunks of ``buffer_rows`` rows, as many chunks as hold a live row (one,
while the routing is even; a dynamic trip count, so nothing is ever
dropped and a skewed step pays for its spill in time, not in memory).
The walk is a ``custom_vjp`` (:func:`_held_chunks`): its backward walks
the same chunks, recomputes each chunk's rows and activations and
differentiates the chunk's three grouped matmuls (megablox's ``gmm`` /
``tgmm`` pair on a TPU, ``ragged_dot``'s own derivative elsewhere), so
neither pass holds a ``T x top_k``-row buffer: tokens reach a chunk by
a gather, and a token sums the rows it holds in the chunk in ONE pass
over the chunk (:func:`_sum_own`: on a TPU the rows are gathered once
into token order and ``apex_moe_combine`` adds each token's run into
the carry in place, so the cost follows the held rows; elsewhere
``top_k`` gathers out of the chunk; no scatter, no ``(T, H)`` array a
slot).  Rows past the live ones are zeroed where they
are read and where they are written, so whatever a grouped matmul
leaves there reaches no output and no gradient.  The router's bias is
choice-only STATE: :func:`balance_bias_update` moves it from the
per-expert load the layer returns, outside every gradient and every
optimizer.  :func:`moe_ffn` above stays the capacity-factor layer
(dropping, ``all_to_all``); the exchange between the chips of an
expert group does not exist yet (ROADMAP, Queue 2).
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
                        topk_group: int, scale: float, eps: float = 1e-20):
    """Bias-corrected, group-limited top-k routing over ALL experts.

    ``x``: (T, H); ``router_w``: (H, E); ``bias``: (E,) — added to the
    scores for the CHOICE only.  In float32 whatever the inputs' dtype:
    ``s = sigmoid(x W)``; the ``n_group`` groups of ``E / n_group``
    consecutive experts are each scored by the sum of their 2 best
    ``s + bias``, the best ``topk_group`` groups stay, and the
    ``top_k`` best ``s + bias`` among them pick the experts.  Their
    weights are the ORIGINAL ``s``, divided by their sum plus ``eps`` and
    multiplied by ``scale``.  Returns ``(ids (T, top_k) int32, weights
    (T, top_k) float32)``.
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
    weights = picked / (picked.sum(-1, keepdims=True) + eps) * scale
    return ids, weights


#: (rows, contraction, columns) tile of the Pallas grouped matmul where
#: the plan (:func:`grouped_tiling`) has no better: ``tgmm`` and a row
#: tile under 128 take it whole, a contraction too deep for one tile
#: its ``tk``.  Until PR 48 every serving call had it (88% of the HBM
#: roofline at 64 live rows over 16 experts of 7168 x 2048 on a v5e,
#: where XLA's own ``ragged_dot`` kernel reached 40%: PERF.md, PR 26).
#: With ``tk`` short of ``k`` the grid's innermost axis turns the rows'
#: and the weights' block index at EVERY grid step: the rows are
#: fetched again a step and a group's matrix again a VISIT (a (group,
#: row tile) pair), twice where its rows straddle a row tile's edge
GROUPED_TILING = (128, 1024, 1024)
#: rows over groups (both static in a call) from which a group spans
#: several row tiles and the row tile is 256: 1.3-102 in the serving
#: programs, 1,280 in the train chunk (PERF.md, PR 46)
RESIDENT_ROWS_A_GROUP = 512
#: what Mosaic lets a kernel's blocks take of VMEM when, as megablox,
#: it asks for no other limit (v5e)
SCOPED_VMEM_BYTES = 16 * 2 ** 20


def grouped_vmem_bytes(product, tiling, itemsize):
    """VMEM a megablox kernel's blocks take at ``tiling``: both operands'
    and the output's blocks twice (the pipeline's two buffers), and the
    float32 accumulator of an output block."""
    tm, tk, tn = tiling
    out = tk * tn if product == "tgmm" else tm * tn
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * out


def grouped_tiling(product, rows, groups, k, n, dtype):
    """``(tm, tk, tn)`` of one grouped matmul, from what the call can
    see; None where no row tile divides ``rows``.  ``product``: "gmm"
    (``rows x w``), "gmm_t" (``rows x w^T``: the rows' cotangent) or
    "tgmm" (``rows^T x cotangent`` a group: the weights' gradient, whose
    ``tm`` tiles its contraction and ``(tk, tn)`` an expert's matrix);
    ``k``, ``n``: the product's contraction and output widths (of
    "tgmm": the matrix's two); ``dtype``: the weights'.

    A Pallas pipeline fetches a block again only when its index moves.
    ``gmm``'s grid is ``(column tiles, visits, contraction tiles)``, a
    VISIT one (group, row tile) pair that shares a row: as many as row
    tiles with a live row plus live groups less one, unless an edge
    falls on a tile's.  Its blocks' indices: rows ``(row tile, k_i)``,
    weights ``(group, k_i, n_i)``, output ``(row tile, n_i)``.  With
    ``tk`` short of ``k`` the innermost axis turns ``k_i`` at every grid
    step, so the rows are fetched again at EVERY grid step and a
    group's matrix streams once a VISIT: once a row tile in the
    training chunk (ten of them a group), twice in a serving call
    wherever a group's rows straddle a row tile's edge, and where
    ``tk`` does not divide ``k`` every visit's last contraction tile
    masks both operands through float32.  So ``gmm`` and ``gmm_t``

    - contract in ONE tile (``tk = k``): the weights' index moves once
      a group (a straddling group's visits follow one another) and the
      rows' once a row tile.  Alone on a v5e, a call: 0.93 -> 0.51 ms in
      the train chunk at 2,048 deep (PR 46); 0.488 -> 0.409 ms at the
      LFM2 decode step (32 groups of 31 rows, 2,048 x 1,792, seven
      groups over an edge: 331 MB moved where 243 are owed), 0.319 ->
      0.257 at Kimi's (2,304 deep: no remainder tile left), 0.592 ->
      0.479 and 0.464 -> 0.340 at their prefills (PR 48);
    - in the fewest EVEN column tiles of at least 512 that fit
      :data:`SCOPED_VMEM_BYTES` by :func:`grouped_vmem_bytes`: with
      ``tn = n`` the rows are read once, not once a column tile (0.55 ->
      0.51 ms at 1,024 deep into 2,048), and two tiles of 896 read
      faster than 1,024 and 768 (0.409 against 0.416 ms);
    - a contraction too deep for that (7,168 beside 512 columns is 17.8
      MiB) stays in tiles of :data:`GROUPED_TILING`'s ``tk`` under the
      columns chosen the same way, all 2,048 at once: 0.714 -> 0.674 ms
      a call, where one tile of 7,168 over 256 columns read 0.679 at
      the decode step and no faster than the parent at a prefill;
    - and from :data:`RESIDENT_ROWS_A_GROUP` rows a group a row tile of
      256 where it divides ``rows`` (another 3% in the train chunk).

    ``tgmm`` keeps the plain tiles: a taller row tile read SLOWER (0.56
    -> 0.61 ms at 512 rows), and the one block that read faster, an
    expert's whole matrix, is past the limit by this arithmetic.  The
    readings: ``benchmarks/grouped_matmul_sweep.py`` on a v5e (PERF.md,
    PRs 46 and 48); which of the tilings that fit is fastest is the
    chip's to say, and the sweep says it again for another shape."""
    tm = next((t for t in (GROUPED_TILING[0], 64, 32, 16, 8)
               if rows % t == 0), None)
    if tm is None:
        return None
    plain = (tm, min(GROUPED_TILING[1], k), min(GROUPED_TILING[2], n))
    if tm < GROUPED_TILING[0] or product == "tgmm":
        return plain
    if rows // groups >= RESIDENT_ROWS_A_GROUP and rows % 256 == 0:
        tm = 256
    # a sixteenth left for what the compiler keeps beside the blocks
    # (0.45 MiB beside a float32 tgmm's 16.0 on the described v5e)
    fits = lambda t: grouped_vmem_bytes(
        product, t, jnp.dtype(dtype).itemsize) <= SCOPED_VMEM_BYTES * 15 // 16
    even = [n // c for c in range(1, max(n // 512, 1) + 1)
            if n % (128 * c) == 0 or c == 1]
    return next((t for tk in (k, plain[1]) for tn in even
                 if fits(t := (tm, tk, tn))), plain)


def _plain(impl) -> bool:
    """Whether ``impl`` asks for the plain XLA form here: "xla", or
    "auto" off a TPU ("pallas" and "interpret" force the kernels)."""
    from apex_tpu.utils.platform import on_tpu

    if impl not in ("auto", "pallas", "interpret", "xla"):
        raise ValueError(f"impl must be 'auto', 'pallas', 'interpret' or "
                         f"'xla'; got {impl!r}")
    return impl == "xla" or (impl == "auto" and not on_tpu())


def _grouped_matmul(rows, w, group_sizes, impl, trainable=False):
    """``rows[sizes[:g].sum() : sizes[:g+1].sum()] @ w[g]`` for every
    group ``g``: ``jax.lax.ragged_dot`` ("xla"), or the Pallas grouped
    GEMM that ships with JAX (megablox ``gmm``; "pallas", "interpret",
    and "auto" on a TPU), which visits only the row tiles that hold a
    live row, at the tiles :func:`grouped_tiling` gives the call.  Rows
    past the live ones come out undefined.
    ``trainable``: the same kernel under a ``custom_vjp``
    (:func:`_gmm_trainable`); the bare ``pallas_call`` has no
    derivative."""
    if _plain(impl) or grouped_tiling("gmm", rows.shape[0], *w.shape,
                                      w.dtype) is None:
        return jax.lax.ragged_dot(rows, w, group_sizes)
    return (_gmm_trainable if trainable else _gmm)(
        rows, w, group_sizes, impl == "interpret")


def _gmm(rows, w, group_sizes, interpret, transposed=False):
    """megablox's ``gmm`` at the call's own tiles: ``rows x w[g]`` a
    group, or (``transposed``) ``rows x w[g]^T``."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    G, K, N = w.shape
    tiling = grouped_tiling("gmm_t" if transposed else "gmm", rows.shape[0],
                            G, *((N, K) if transposed else (K, N)), w.dtype)
    return gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
               tiling=tiling, transpose_rhs=transposed, interpret=interpret)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_trainable(rows, w, group_sizes, interpret):
    """:func:`_gmm` with a derivative, as megablox's own ``ops.gmm`` has
    one: ``gmm`` against the weights transposed for the rows' cotangent
    and ``tgmm`` for the weights'.  ``ops.gmm`` hands ONE tiling to all
    three kernels; here each gets its own (:func:`grouped_tiling`)."""
    return _gmm(rows, w, group_sizes, interpret)


def _gmm_trainable_fwd(rows, w, group_sizes, interpret):
    return _gmm(rows, w, group_sizes, interpret), (rows, w, group_sizes)


def _gmm_trainable_bwd(interpret, res, dout):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    rows, w, group_sizes = res
    dw = tgmm(rows.swapaxes(0, 1), dout, group_sizes, w.dtype,
              grouped_tiling("tgmm", rows.shape[0], *w.shape, w.dtype),
              num_actual_groups=w.shape[0], interpret=interpret)
    return _gmm(dout, w, group_sizes, interpret, transposed=True), dw, None


_gmm_trainable.defvjp(_gmm_trainable_fwd, _gmm_trainable_bwd)


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


# ------------------------------------------- held experts (training)
def _chunk(c, rows_per_chunk, order, offsets, n_live):
    """Chunk ``c`` of the sorted held assignments: the assignment (token
    * top_k + its slot) of each of its rows, which rows are live, and
    how many rows each held expert has inside it."""
    start = c * rows_per_chunk
    pos = start + jnp.arange(rows_per_chunk, dtype=jnp.int32)
    assignment = jax.lax.dynamic_slice(order, (start,), (rows_per_chunk,))
    inside = jnp.clip(offsets - start, 0, rows_per_chunk)
    return assignment, pos < n_live, inside[1:] - inside[:-1]


def _chunk_ffn(rows, valid, sizes, w_gate, w_up, w_down, impl,
               trainable=True):
    """One chunk's experts: the gated FFN over its rows, with every row
    past the live ones zero where it is read and where it is written."""
    rows = jnp.where(valid[:, None], rows, 0)
    gate = _grouped_matmul(rows, w_gate, sizes, impl, trainable)
    up = _grouped_matmul(rows, w_up, sizes, impl, trainable)
    act = jnp.where(valid[:, None], jax.nn.silu(gate) * up, 0)
    y = _grouped_matmul(act, w_down, sizes, impl, trainable)
    return jnp.where(valid[:, None], y, 0)


def _rows_of(slot, first_row, n_rows):
    """Where each assignment sits in a chunk of ``n_rows`` rows that
    starts at rank ``first_row`` (``slot``: (T, top_k) ranks in the
    sorted order), or ``n_rows``, past the end, where it does not."""
    local = slot - first_row
    return jnp.where((local >= 0) & (local < n_rows), local, n_rows)


def _own(values, slot, first_row):
    """``values`` (rows of a chunk) laid back out by assignment: (T,
    top_k), 0 where the assignment is outside the chunk."""
    return jnp.take(values, _rows_of(slot, first_row, values.shape[0]),
                    axis=0, mode="fill", fill_value=0)


def _sum_own(out, rows, w, token, valid, slot, first_row, impl):
    """``out`` (T, H) float32 plus each token's sum, in float32, over
    the rows of the chunk it holds, each ``float32(rows[r]) * w[r]``
    (``w`` None: 1); an assignment outside the chunk adds nothing and a
    dead row (``valid`` false) is never read, whatever it holds.
    ``rows`` (R, H) as the grouped matmul left them, ``token`` (R,) the
    token of each, ``slot`` (T, top_k) each assignment's rank in the
    sorted order, ``first_row`` the chunk's first rank.

    On a TPU (``impl`` as :func:`_grouped_matmul` reads it) ONE pass
    over the chunk: the rows gathered once into token order and each
    token's run added into ``out`` in place
    (:func:`apex_tpu.ops.moe_combine_pallas.moe_combine_pallas`), so a
    call costs what the held rows cost.  Elsewhere ``top_k`` gathers of
    (T, H) out of the chunk, added in slot order (all at once would be
    the ``T x top_k``-row buffer again); no scatter either way."""
    from apex_tpu.ops import moe_combine_pallas as kernel

    n_tokens, n_rows = out.shape[0], rows.shape[0]
    if not _plain(impl) and kernel.token_block(n_tokens) \
            and rows.dtype in (jnp.bfloat16, jnp.float32):
        return kernel.moe_combine_pallas(
            out, rows, w, jnp.where(valid, token, n_tokens),
            interpret=(impl == "interpret"))
    local = _rows_of(slot, first_row, n_rows)
    local = jnp.where(local < jnp.sum(valid), local, n_rows)
    total = 0.0
    for k in range(slot.shape[1]):
        own = jnp.take(rows, local[:, k], axis=0, mode="fill",
                       fill_value=0).astype(jnp.float32)
        if w is not None:
            own = own * jnp.take(w, local[:, k], mode="fill",
                                 fill_value=0)[:, None]
        total = total + own
    return out + total


@partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _held_chunks(x, weights, w_gate, w_up, w_down, order, slot, offsets,
                 n_live, rows_per_chunk, impl):
    return _held_chunks_fwd(x, weights, w_gate, w_up, w_down, order, slot,
                            offsets, n_live, rows_per_chunk, impl)[0]


def _held_chunks_fwd(x, weights, w_gate, w_up, w_down, order, slot, offsets,
                     n_live, rows_per_chunk, impl, layer=None):
    """``x`` (T, H); ``weights`` (T, top_k) float32, 0 where an
    assignment is not held; ``order`` (T*top_k,) the assignments sorted
    held-first by expert; ``slot`` (T, top_k) each assignment's rank in
    that order (``>= n_live``: not held); ``offsets`` (n_held + 1,) the
    experts' first ranks.  Returns ``(T, H)`` float32.

    ``layer`` (a traced index): the serving form, forward only.  The
    weights are then the STACKED layers' experts side by side as groups
    (``(layers * n_held, ...)``, never sliced), of which only this
    layer's get rows."""
    top_k = weights.shape[1]
    flat_w = weights.reshape(-1)
    n_held = offsets.shape[0] - 1

    def body(c, out):
        assignment, valid, sizes = _chunk(c, rows_per_chunk, order, offsets,
                                          n_live)
        if layer is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((w_gate.shape[0],), jnp.int32), sizes,
                (jnp.asarray(layer, jnp.int32) * n_held,))
        y = _chunk_ffn(jnp.take(x, assignment // top_k, axis=0), valid,
                       sizes, w_gate, w_up, w_down, impl,
                       trainable=layer is None)
        w = jnp.where(valid, jnp.take(flat_w, assignment), 0.0)
        return _sum_own(out, y, w, assignment // top_k, valid, slot,
                        c * rows_per_chunk, impl)

    n_chunks = -(-n_live // rows_per_chunk)
    out = jax.lax.fori_loop(0, n_chunks, body,
                            jnp.zeros(x.shape, jnp.float32))
    return out, (x, weights, w_gate, w_up, w_down, order, slot, offsets,
                 n_live)


def _held_chunks_bwd(rows_per_chunk, impl, res, dout):
    x, weights, w_gate, w_up, w_down, order, slot, offsets, n_live = res
    top_k = weights.shape[1]
    flat_w = weights.reshape(-1)
    dout = dout.astype(jnp.float32)

    def body(c, carry):
        dx, dweights, dgate, dup, ddown = carry
        assignment, valid, sizes = _chunk(c, rows_per_chunk, order, offsets,
                                          n_live)
        token = assignment // top_k
        y, vjp = jax.vjp(
            lambda r, wg, wu, wd: _chunk_ffn(r, valid, sizes, wg, wu, wd,
                                             impl),
            jnp.take(x, token, axis=0), w_gate, w_up, w_down)
        g = jnp.where(valid[:, None], jnp.take(dout, token, axis=0), 0.0)
        w = jnp.where(valid, jnp.take(flat_w, assignment), 0.0)
        # the routing weight's cotangent: its row of y against the
        # token's cotangent
        dw_rows = jnp.sum(y.astype(jnp.float32) * g, axis=-1)
        drows, dg, du, dd = vjp((g * w[:, None]).astype(y.dtype))
        first = c * rows_per_chunk
        return (_sum_own(dx, drows, None, token, valid, slot, first, impl),
                dweights + _own(dw_rows, slot, first),
                dgate + dg.astype(jnp.float32), dup + du.astype(jnp.float32),
                ddown + dd.astype(jnp.float32))

    zeros32 = lambda a: jnp.zeros(a.shape, jnp.float32)
    n_chunks = -(-n_live // rows_per_chunk)
    dx, dweights, dgate, dup, ddown = jax.lax.fori_loop(
        0, n_chunks, body, (zeros32(x), zeros32(weights), zeros32(w_gate),
                            zeros32(w_up), zeros32(w_down)))
    return (dx.astype(x.dtype), dweights.astype(weights.dtype),
            dgate.astype(w_gate.dtype), dup.astype(w_up.dtype),
            ddown.astype(w_down.dtype), None, None, None, None)


_held_chunks.defvjp(_held_chunks_fwd, _held_chunks_bwd)


def expert_buffer_rows(tokens: int, top_k: int, n_held: int, n_experts: int,
                       factor: float = 1.25, multiple: int = 512) -> int:
    """Rows of the static chunk an expert layer walks (``buffer_rows``):
    the held share of ``tokens * top_k`` assignments under even routing
    times ``factor``, up to a multiple the grouped matmul tiles, and
    never more than every assignment."""
    share = tokens * top_k * n_held / n_experts
    rows = -(-int(share * factor) // multiple) * multiple
    return max(multiple, min(rows, -(-tokens * top_k // multiple) * multiple))


def balance_bias_update(bias, load, coeff: float):
    """Auxiliary-loss-free balancing (Wang et al., arXiv:2408.15664):
    ``b_e <- b_e + coeff * sign(mean(load) - load_e)`` from the
    assignments each of ALL the router's experts got in the step.  The
    bias only corrects the choice, carries no gradient and belongs to
    no optimizer's tree; the step applies this after the optimizer."""
    load = load.astype(jnp.float32)
    return bias + coeff * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load).astype(bias.dtype)


def held_experts_ffn(x, params, held: range, *, top_k: int, n_group: int,
                     topk_group: int, scale: float, token_mask=None,
                     layer=None, impl="auto", buffer_rows=None,
                     softmax=False, eps=1e-20):
    """The routed part of an expert layer that the experts ``held``
    give, for every token, with no assignment dropped.

    ``x``: (T, H).  ``params``: ``router`` (H, E) and ``router_bias``
    (E,) over ALL ``E`` experts; ``we_gate``/``we_up`` (n_held, H, F)
    and ``we_down`` (n_held, F, H), the held experts' weights in id
    order.  ``held``: the static ``range`` of ids held
    (``range(E)``: the whole layer).  ``token_mask``: (T,) bool —
    tokens that are padding or an empty slot route nowhere.

    Each token's ``top_k`` assignments are chosen and weighted over all
    experts (:func:`route_group_limited`, its ``eps``; ``softmax``:
    :func:`route_softmax`); those to a held expert are sorted and run through
    :func:`grouped_gated_ffn` in a static buffer of ``T * top_k`` rows,
    the worst case.  Returns ``(out (T, H), counts)`` with ``counts``
    the int32 scalars ``assignments_held`` (assignments computed
    here), ``assignments_all`` (``top_k`` a live token) and
    ``experts_hit`` (held experts with at least one).

    ``buffer_rows`` (a static int; :func:`expert_buffer_rows`): the
    held assignments, compacted by the sort, are walked in chunks of
    ``buffer_rows`` rows, as many as hold a live row: nothing is
    dropped whatever the routing and no buffer has ``T * top_k`` rows,
    so a call costs what the held share of the assignments costs (an
    eighth of them where 16 of 128 experts are held), not what all of
    them would.  With one layer's experts it is the trainable form,
    under a ``custom_vjp`` whose backward walks the chunks again
    (:func:`_held_chunks`); with a stack and its ``layer`` the serving
    form, forward only.  ``counts`` then also holds
    ``load`` (E,) int32, the assignments every expert of the router got
    (what :func:`balance_bias_update` reads), ``spill_chunks`` (chunks
    walked beyond the first) and ``buffer_rows`` (rows of the chunks
    walked, the first always).
    """
    T, H = x.shape
    n_held = len(held)
    experts = {k: params[k] for k in ("we_gate", "we_up", "we_down")}
    stacked = layer is not None
    if not stacked:
        experts = {k: w[None] for k, w in experts.items()}
        layer = 0
    n_layers = experts["we_gate"].shape[0]
    if held.step != 1 or experts["we_gate"].shape[1] != n_held:
        raise ValueError(
            f"held {held} must be a contiguous range matching the "
            f"{experts['we_gate'].shape[1]} experts' weights given")
    if softmax:
        ids, weights = route_softmax(x, params["router"], top_k=top_k,
                                     scale=scale)
    else:
        ids, weights = route_group_limited(
            x, params["router"], params["router_bias"], top_k=top_k,
            n_group=n_group, topk_group=topk_group, scale=scale, eps=eps)
    live = (ids >= held.start) & (ids < held.stop)
    if token_mask is not None:
        live = live & token_mask[:, None]
    A = T * top_k
    local = jnp.where(live, ids - held.start, n_held).reshape(A)
    order = jnp.argsort(local, stable=True)           # held first, by expert
    group_sizes = jnp.sum(
        local[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None],
        axis=0, dtype=jnp.int32)

    def counted():
        n_tokens = T if token_mask is None else jnp.sum(token_mask)
        return {
            "assignments_held": jnp.sum(group_sizes),
            "assignments_all": jnp.asarray(n_tokens * top_k, jnp.int32),
            "experts_hit": jnp.sum(group_sizes > 0, dtype=jnp.int32),
        }

    # the layers' experts side by side as groups; only this layer's
    # have rows
    flat = lambda k: experts[k].reshape(
        (n_layers * n_held,) + experts[k].shape[2:])
    if buffer_rows is not None:
        # every expert's load, token by token (no T * top_k-row
        # one-hot); the held experts' group sizes are its slice
        chosen = ids if token_mask is None else jnp.where(
            token_mask[:, None], ids, -1)
        every = jnp.arange(params["router"].shape[-1], dtype=jnp.int32)
        load = jnp.sum(
            jnp.sum(chosen[:, :, None] == every[None, None], axis=1,
                    dtype=jnp.int32), axis=0, dtype=jnp.int32)
        group_sizes = load[held.start:held.stop]
        counts = counted()
        # each assignment's rank in the sorted order
        slot = jnp.zeros((A,), jnp.int32).at[order].set(
            jnp.arange(A, dtype=jnp.int32)).reshape(T, top_k)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(group_sizes)])
        # the buffer walks whole chunks: pad the order to one
        rows_per_chunk = int(buffer_rows)
        padded = -(-A // rows_per_chunk) * rows_per_chunk
        walk = (x, jnp.where(live, weights, 0.0),
                *(flat(k) if stacked else experts[k][0]
                  for k in ("we_gate", "we_up", "we_down")),
                jnp.pad(order, (0, padded - A)), slot, offsets,
                counts["assignments_held"], rows_per_chunk, impl)
        out = _held_chunks_fwd(*walk, layer=layer)[0] if stacked \
            else _held_chunks(*walk)
        counts.update(
            load=load,
            spill_chunks=jnp.maximum(
                -(-counts["assignments_held"] // rows_per_chunk) - 1, 0))
        counts["buffer_rows"] = rows_per_chunk * (1 + counts["spill_chunks"])
        return out.astype(x.dtype), counts
    rows = jnp.take(x, order // top_k, axis=0)        # (A, H)
    all_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * n_held,), jnp.int32), group_sizes,
        (jnp.asarray(layer, jnp.int32) * n_held,))
    y = grouped_gated_ffn(rows, flat("we_gate"), flat("we_up"),
                          flat("we_down"), all_sizes, impl=impl)
    # a row past the live ones belongs to no group: whatever the grouped
    # matmul left there is replaced, not multiplied away
    w = jnp.take(jnp.where(live, weights, 0.0).reshape(A), order)
    y = jnp.where(w[:, None] > 0, y.astype(jnp.float32) * w[:, None], 0.0)
    # back to assignment order, then each token sums its own
    back = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.arange(A, dtype=jnp.int32))
    out = jnp.take(y, back, axis=0).reshape(T, top_k, H).sum(1)
    return out.astype(x.dtype), counted()


def route_softmax(x, router_w, *, top_k: int, scale: float = 1.0):
    """Softmax top-k routing over ALL experts, no bias and no groups.

    ``x``: (T, H); ``router_w``: (H, E).  In float32 whatever the
    inputs' dtype: ``p = softmax(x W)`` over the ``E`` experts, the
    ``top_k`` largest chosen (ties: the lowest id), their weights ``p``
    divided by the chosen ones' sum (``norm_topk_prob``) and multiplied
    by ``scale``.  Returns ``(ids (T, top_k) int32, weights (T, top_k)
    float32)``, as :func:`route_group_limited`."""
    p = jax.nn.softmax(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32)), axis=-1)
    picked, ids = jax.lax.top_k(p, top_k)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), weights
