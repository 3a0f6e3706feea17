"""Kimi Delta Attention (KDA): the gated delta rule with a decay of its
own for every key channel, as a recurrent state.

A head keeps ``S`` (``dk x dv``, float32).  A token with query ``q``
and key ``k`` (both L2-normalised by the caller, ``q`` scaled), value
``v``, log-decay ``g <= 0`` (one a KEY CHANNEL) and write strength
``beta`` in (0, 1) does::

    S' = Diag(exp(g)) S
    S  = S' + beta k (v - S'^T k)^T
    o  = S^T q

Nothing grows with the context: ``S`` is all that a sequence carries.
Three forms of the one recurrence, all float32:

- :func:`kda_recurrent` — token by token under ``lax.scan``: the
  numerics specification of the other two (and far too slow for a
  prompt on the chip);
- :func:`kda_chunked` — the prompt, in chunks of :data:`CHUNK`: inside
  a chunk the delta rule is a unit-lower-triangular solve on the
  ``beta``- and decay-weighted ``K K^T`` (done for all chunks at once,
  in XLA), between chunks the state is carried (:func:`chunk_scan`: a
  ``lax.scan`` in XLA, on the chip the kernel ``apex_kda_chunk_scan``);
- :func:`kda_decode` — one token a slot on the stacked per-slot state
  ``(layers, slots + 1, H, dk, dv)``, in place: the kernel
  ``apex_kda_decode`` reads an ACTIVE slot's state, updates it and
  writes it back through input/output aliasing; an inactive slot's grid
  steps go to the garbage row (the last), so its own state is neither
  read nor written.  :func:`conv_step` (``apex_kda_conv_step``) does
  the same for the short convolution in front of it: one output a slot
  from the slot's cached tail, and the tail shifted, in place.
  :func:`install_rows` (``apex_slot_install``) puts a prefill's final
  values into one slot's rows of such an array, in place.  These two
  serve BOTH recurrences of the package: the Mamba-2 mixer
  (:mod:`apex_tpu.ops.ssd`, ``models/falcon_h1.py``) calls them with its
  own channel count and filter, and adds its convolution's bias to the
  sum it gets back (the kernels keep their names).

**Decays without overflow.**  With ``G`` the running sum of ``g``
inside a chunk, the chunk's matrices hold ``exp(G_t - G_s)`` for ``s <=
t``: never above 1, but factored naively as ``exp(G_t) exp(-G_s)`` the
second factor overflows after a few strongly decayed steps.  The chunk
is cut into sub-blocks of :data:`SUB` rows and every decay is taken
relative to the FIRST ROW ``n`` of the sub-block that holds ``t``:
``exp(G_t - G_n)`` is at most 1, ``exp(G_n - G_s)`` is at most 1 for
every earlier sub-block, and inside ``t``'s own it is bounded by the
decay of :data:`SUB` steps (clamped at ``e^80``: exact unless a key
channel decays by more than 80 nats within 16 tokens, where the
products it scales have left float32 anyway).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["CHUNK", "SUB", "chunk_scan", "conv_step", "conv_step_xla",
           "install_rows", "kda_chunked", "kda_decode", "kda_decode_xla",
           "kda_recurrent"]

#: positions a chunk of :func:`kda_chunked` holds
CHUNK = 64
#: rows of a sub-block: the span over which a decay is taken relative
SUB = 16
_CLAMP = 80.0
_HI = jax.lax.Precision.HIGHEST


def _dispatch(name, impl, kernel_impl, xla_impl):
    """The kernel where ``impl`` forces it or ("auto") a TPU is there,
    its XLA form elsewhere (``decode_attention_pallas.dispatch_kernel``:
    a chosen kernel degrades once through the fallback registry)."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_kernel
    from apex_tpu.utils.platform import on_tpu

    return dispatch_kernel(name, impl, on_tpu, kernel_impl, xla_impl)


# --------------------------------------------------------------- recurrence
def _step(S, q, k, v, g, beta):
    """One token of one or more heads: ``S`` (..., dk, dv); ``q``, ``k``,
    ``g`` (..., dk); ``v`` (..., dv); ``beta`` (...,)."""
    S = S * jnp.exp(g)[..., :, None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def kda_recurrent(q, k, v, g, beta, state):
    """The recurrence, token by token.  ``q``, ``k``, ``g``: (T, H,
    dk); ``v``: (T, H, dv); ``beta``: (T, H); ``state``: (H, dk, dv).
    Returns ``(o (T, H, dv), state)``, float32."""
    f = lambda x: x.astype(jnp.float32)

    def body(S, x):
        S, o = _step(S, *x)
        return S, o

    state, o = jax.lax.scan(body, f(state),
                            (f(q), f(k), f(v), f(g), f(beta)))
    return o, state


# ------------------------------------------------------------------ chunked
def _unit_lower_inverse(N):
    """``(I + N)^-1`` for strictly lower-triangular ``N`` (..., C, C),
    ``C`` a multiple of :data:`SUB`: the diagonal sub-blocks by the
    finite product ``(I - N)(I + N^2)(I + N^4)(I + N^8)`` (``N^16 =
    0``), the rest by block forward substitution.  Matmuls only."""
    C = N.shape[-1]
    nb = C // SUB
    eye = jnp.eye(SUB, dtype=N.dtype)
    mm = functools.partial(jnp.matmul, precision=_HI)
    blk = lambda i, j: N[..., i * SUB:(i + 1) * SUB, j * SUB:(j + 1) * SUB]
    rows = []
    for i in range(nb):
        D = blk(i, i)
        inv, P = eye - D, mm(D, D)
        for _ in range(max(SUB.bit_length() - 2, 0)):
            inv, P = mm(inv, eye + P), mm(P, P)
        row = []
        for j in range(i):
            acc = sum(mm(blk(i, m), rows[m][j]) for m in range(j, i))
            row.append(-mm(inv, acc))
        row.append(inv)
        row.extend([jnp.zeros_like(inv)] * (nb - 1 - i))
        rows.append(row)
    return jnp.concatenate([jnp.concatenate(r, axis=-1) for r in rows],
                           axis=-2)


def _chunk_operands(q, k, v, g, beta):
    """Everything of :func:`kda_chunked` that does not depend on the
    carried state, for all chunks at once.  Inputs (H, N, C, d) /
    ``beta`` (H, N, C), float32.  Returns ``W``, ``U0`` (the solve
    applied to the decayed keys and to the values), ``Qg`` (queries
    decayed from the chunk's start), ``Kend`` (keys decayed to its
    end), ``B`` (C, C; the decayed ``Q K^T``, lower triangle with the
    diagonal) and ``gamma`` (the chunk's whole decay, (H, N, dk))."""
    H, N, C, dk = k.shape
    nb = C // SUB
    G = jnp.cumsum(g, axis=2)
    first = G.reshape(H, N, nb, SUB, dk)[:, :, :, 0]          # (H,N,nb,dk)
    own = jnp.repeat(first, SUB, axis=2)                      # (H,N,C,dk)
    fall = jnp.exp(G - own)                                   # <= 1
    # row s of a chunk, seen from sub-block i's first row
    rise = jnp.exp(jnp.minimum(first[:, :, :, None] - G[:, :, None],
                               _CLAMP))                       # (H,N,nb,C,dk)
    kr = k[:, :, None] * rise
    sub = lambda x: (x * fall).reshape(H, N, nb, SUB, dk)
    A = jnp.einsum("hnird,hnisd->hnirs", sub(k), kr,
                   precision=_HI).reshape(H, N, C, C)
    B = jnp.einsum("hnird,hnisd->hnirs", sub(q), kr,
                   precision=_HI).reshape(H, N, C, C)
    t = jnp.arange(C)
    A = jnp.where(t[:, None] > t[None, :], A, 0.0)
    B = jnp.where(t[:, None] >= t[None, :], B, 0.0)
    T = _unit_lower_inverse(beta[..., None] * A)
    decayed = jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([k * decayed, v], axis=-1)
    solved = jnp.matmul(T, rhs, precision=_HI)
    last = G[:, :, -1]
    return (solved[..., :dk], solved[..., dk:], q * decayed,
            k * jnp.exp(last[:, :, None] - G), B, jnp.exp(last))


def _chunk_scan_xla(W, U0, Qg, Kend, B, gamma, state):
    mm = functools.partial(jnp.matmul, precision=_HI)

    def body(S, x):
        w, u0, qg, kend, b, gam = x
        u = u0 - mm(w, S)
        o = mm(qg, S) + mm(b, u)
        S = S * gam[:, :, None] + mm(jnp.swapaxes(kend, -1, -2), u)
        return S, o

    lead = lambda x: jnp.moveaxis(x, 1, 0)
    state, o = jax.lax.scan(
        body, state, tuple(lead(x) for x in (W, U0, Qg, Kend, B, gamma)))
    return jnp.moveaxis(o, 0, 1), state


def _kda_chunk_scan_kernel(w_ref, u0_ref, qg_ref, kend_ref, b_ref, gam_ref,
                       s0_ref, o_ref, s_ref, st_ref, *, chunks):
    """One head a row of the grid, its chunks in turn; the state rides
    TRANSPOSED (dv, dk) in scratch, so that the chunk's decay is a row
    that broadcasts over sublanes and every product is a plain, an NT
    or a TN matmul."""
    n = pl.program_id(1)
    dot = functools.partial(jax.lax.dot_general, precision=_HI,
                            preferred_element_type=jnp.float32)
    nt = (((1,), (1,)), ((), ()))

    @pl.when(n == 0)
    def _load():
        st_ref[:] = s0_ref[0]

    St = st_ref[:]
    u = u0_ref[0, 0] - dot(w_ref[0, 0], St, nt)
    o_ref[0, 0] = dot(qg_ref[0, 0], St, nt) \
        + dot(b_ref[0, 0], u, (((1,), (0,)), ((), ())))
    St = St * gam_ref[0, 0] + dot(u, kend_ref[0, 0],
                                  (((0,), (0,)), ((), ())))
    st_ref[:] = St

    @pl.when(n == chunks - 1)
    def _store():
        s_ref[0] = St


def _chunk_scan_pallas(W, U0, Qg, Kend, B, gamma, state, interpret=False):
    H, N, C, dk = W.shape
    dv = U0.shape[-1]
    at = lambda h, n: (h, n, 0, 0)
    head = lambda h, n: (h, 0, 0)
    o, St = pl.pallas_call(
        functools.partial(_kda_chunk_scan_kernel, chunks=N),
        grid=(H, N),
        in_specs=[pl.BlockSpec((1, 1, C, dk), at),
                  pl.BlockSpec((1, 1, C, dv), at),
                  pl.BlockSpec((1, 1, C, dk), at),
                  pl.BlockSpec((1, 1, C, dk), at),
                  pl.BlockSpec((1, 1, C, C), at),
                  pl.BlockSpec((1, 1, 1, dk), at),
                  pl.BlockSpec((1, dv, dk), head)],
        out_specs=[pl.BlockSpec((1, 1, C, dv), at),
                   pl.BlockSpec((1, dv, dk), head)],
        out_shape=[jax.ShapeDtypeStruct((H, N, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="apex_kda_chunk_scan",
    )(W, U0, Qg, Kend, B, gamma[:, :, None], jnp.swapaxes(state, -1, -2))
    return o, jnp.swapaxes(St, -1, -2)


def chunk_scan(W, U0, Qg, Kend, B, gamma, state, impl="auto"):
    """Carry ``state`` (H, dk, dv) through the chunks: for each, ``U =
    U0 - W S``, ``O = Qg S + B U``, ``S = Diag(gamma) S + Kend^T U``.
    Returns ``(O (H, N, C, dv), state)``."""
    args = (W, U0, Qg, Kend, B, gamma, state)
    return _dispatch(
        "kda_chunk_scan", impl,
        lambda: _chunk_scan_pallas(*args, interpret=(impl == "interpret")),
        lambda: _chunk_scan_xla(*args))


#: heads :func:`kda_chunked` solves and carries at a time
CHUNK_HEADS = 8


def kda_chunked(q, k, v, g, beta, state, impl="auto"):
    """:func:`kda_recurrent` of a whole sequence, chunk by chunk (module
    doc).  Shapes as there; ``T`` is padded to a multiple of
    :data:`CHUNK` with positions that leave the state untouched
    (``beta = 0``, ``g = 0``), which is also how a caller marks padding
    of its own.  The heads go :data:`CHUNK_HEADS` at a time through one
    loop (``lax.map``) that holds BOTH halves, the solve inside the
    chunks and the carry between them: a quarter of the float32
    temporaries of all 32 heads at once, and in a device trace the loop
    is the one operation that names the whole of the chunked delta rule
    (the XLA half has no name of its own there).  Returns ``(o (T, H,
    dv), state)``, float32."""
    T, H, dk = k.shape
    pad = -T % CHUNK
    N = (T + pad) // CHUNK
    hb = next(d for d in range(min(CHUNK_HEADS, H), 0, -1) if H % d == 0)

    def chunks(x):
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = jnp.moveaxis(x, 0, 1).reshape((H, N, CHUNK) + x.shape[2:])
        return x.reshape((H // hb, hb) + x.shape[1:])

    def heads(x):
        *operands, s0 = x
        return chunk_scan(*_chunk_operands(*operands), s0, impl=impl)

    s0 = state.astype(jnp.float32)
    o, state = jax.lax.map(heads, (
        chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta),
        s0.reshape((H // hb, hb) + s0.shape[1:])))
    return jnp.moveaxis(o.reshape(H, N * CHUNK, -1), 0, 1)[:T], \
        state.reshape(s0.shape)


# ------------------------------------------------------------------- decode
def kda_decode_xla(q, k, v, g, beta, state, active, layer):
    """One token a slot on the stacked state, in XLA (the CPU path and
    the numerics specification; on the chip the update would copy the
    stacked state).  ``q``, ``k``, ``g``: (B, H, dk); ``v``: (B, H,
    dv); ``beta``: (B, H); ``state``: (L, B + 1, H, dk, dv) float32;
    ``active``: (B,) bool; ``layer``: scalar.  Returns ``(o (B, H, dv),
    state)``: an inactive slot's row comes out 0 and its state as it
    was."""
    B = q.shape[0]
    f = lambda x: x.astype(jnp.float32)
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new, o = _step(S[:B], f(q), f(k), f(v), f(g), f(beta))
    keep = active[:, None, None, None]
    S = S.at[:B].set(jnp.where(keep, new, S[:B]))
    state = jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)
    return jnp.where(active[:, None, None], o, 0.0), state


def _kda_decode_kernel(layer_ref, row_ref, live_ref, cols_ref, v_ref, beta_ref,
                   s_ref, o_ref, out_ref, *, heads):
    """One (slot, block of heads) a grid step.  ``cols_ref`` holds the
    block's decays, keys and queries as ROWS; one transpose of the tile
    turns them into the columns that scale the state's rows."""
    del layer_ref, row_ref   # consumed by the index maps
    live = live_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0].T                       # (dk, 128)
    for h in range(heads):
        decay, k, q = (cols[:, i * heads + h:i * heads + h + 1]
                       for i in range(3))         # (dk, 1) each
        S = s_ref[0, 0, h] * decay
        u = beta_ref[0, 0, h:h + 1] * (
            v_ref[0, h:h + 1] - jnp.sum(S * k, axis=0, keepdims=True))
        S = S + k * u
        out_ref[0, 0, h] = jnp.where(live, S, 0.0)
        o_ref[0, h:h + 1] = jnp.where(
            live, jnp.sum(S * q, axis=0, keepdims=True), 0.0)


#: heads a grid step of ``apex_kda_decode`` holds: 8 x (128 x 128)
#: float32 is 512 KB a block, in and out and double-buffered 2 MB
DECODE_HEADS = 8


def _decode_pallas(q, k, v, g, beta, state, active, layer, interpret=False):
    B, H, dk = q.shape
    dv = v.shape[-1]
    L, rows = state.shape[:2]
    hb = next(d for d in range(min(DECODE_HEADS, H), 0, -1) if H % d == 0)
    if rows != B + 1:
        raise ValueError(
            f"apex_kda_decode: state {state.shape} for {B} slots: needs "
            f"slots + 1 rows (the last is the garbage row)")
    f = lambda x: x.astype(jnp.float32)
    # a block's decays, keys and queries side by side as rows of one
    # (128, dk) tile (the kernel transposes it once)
    cols = jnp.stack([jnp.exp(f(g)), f(k), f(q)], axis=1) \
        .reshape(B, 3, H // hb, hb, dk).transpose(0, 2, 1, 3, 4) \
        .reshape(B, H // hb, 3 * hb, dk)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, 128 - 3 * hb), (0, 0)))
    live = active.astype(jnp.int32)
    row = jnp.where(active, jnp.arange(B, dtype=jnp.int32), B)
    here = lambda b, j, layer_ref, row_ref, live_ref: (b, j, 0)
    state_spec = pl.BlockSpec(
        (1, 1, hb, dk, dv),
        lambda b, j, layer_ref, row_ref, live_ref: (
            layer_ref[0], row_ref[b], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // hb),
        in_specs=[
            pl.BlockSpec((1, 1, 128, dk),
                         lambda b, j, *_: (b, j, 0, 0)),
            pl.BlockSpec((1, hb, dv), here),
            pl.BlockSpec((1, 1, hb, 1),
                         lambda b, j, *_: (b, j, 0, 0)),
            state_spec],
        out_specs=[pl.BlockSpec((1, hb, dv), here), state_spec])
    o, state = pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand numbering counts the three prefetched scalars
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="apex_kda_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row, live, cols, f(v),
      f(beta).reshape(B, H // hb, hb, 1), state)
    return o, state


def kda_decode(q, k, v, g, beta, state, active, layer, impl="auto"):
    """One token a slot on the stacked per-slot state, IN PLACE: the
    one dispatch between ``apex_kda_decode`` and
    :func:`kda_decode_xla` (shapes there).  A chosen kernel degrades
    once through the fallback registry ("kda_decode")."""
    args = (q, k, v, g, beta, state, active, layer)
    return _dispatch(
        "kda_decode", impl,
        lambda: _decode_pallas(*args, interpret=(impl == "interpret")),
        lambda: kda_decode_xla(*args))


# --------------------------------------------------------- short convolution
#: slots a grid step of ``apex_kda_conv_step`` (and of a 1-D
#: ``apex_slot_install``) holds: a whole sublane tile of bfloat16 rows
CONV_SLOTS = 16


def conv_step_xla(x, w, tails, active, layer):
    """One step of a causal depthwise convolution a slot, in XLA (the
    CPU path and the numerics specification).  ``x``: (B, C) the
    current inputs; ``w``: (K, C) the filter, ``w[K - 1]`` the current
    input's tap; ``tails``: (L, B + 1, (K - 1) * C) the last ``K - 1``
    inputs a slot and layer, oldest first, side by side; ``active``:
    (B,) bool; ``layer``: scalar.  Returns ``(y (B, C) float32,
    tails)``: ``y = sum_j w[j] window[j]`` over the tail and ``x``; an
    active slot's tail is shifted by ``x``, an inactive one's is as it
    was."""
    B, C = x.shape
    old = jax.lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False)[:B]
    window = jnp.concatenate([old, x.astype(old.dtype)], axis=1)
    y = jnp.sum(w.astype(jnp.float32)[None]
                * window.reshape(B, -1, C).astype(jnp.float32), axis=1)
    new = jnp.where(active[:, None], window[:, C:], old)
    return y, jax.lax.dynamic_update_slice(tails, new[None], (layer, 0, 0))


def _kda_conv_step_kernel(layer_ref, x_ref, live_ref, w_ref, t_ref, y_ref,
                      out_ref, *, taps, width):
    del layer_ref
    x, old = x_ref[...], t_ref[0]                 # (n, C), (n, taps * C)
    y = w_ref[taps:taps + 1].astype(jnp.float32) * x.astype(jnp.float32)
    for j in range(taps):
        y += w_ref[j:j + 1].astype(jnp.float32) \
            * old[:, j * width:(j + 1) * width].astype(jnp.float32)
    y_ref[...] = y
    new = jnp.concatenate([old[:, width:], x.astype(old.dtype)], axis=1)
    out_ref[0] = jnp.where(live_ref[...] != 0, new, old)


def _conv_step_pallas(x, w, tails, active, layer, interpret=False):
    B, C = x.shape
    L, rows, flat = tails.shape
    taps = w.shape[0] - 1
    if rows != B + 1 or flat != taps * C or w.shape[1] != C:
        raise ValueError(
            f"conv_step: tails {tails.shape} / filter {w.shape} for {B} "
            f"slots of {C} inputs: needs slots + 1 rows of (K - 1) * C")
    n = min(CONV_SLOTS, B)
    tail_spec = pl.BlockSpec((1, n, flat),
                             lambda b, layer_ref: (layer_ref[0], b, 0))
    rows_of = lambda width: pl.BlockSpec((n, width), lambda b, _: (b, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(pl.cdiv(B, n),),
        in_specs=[rows_of(C), rows_of(1),
                  pl.BlockSpec((taps + 1, C), lambda b, _: (0, 0)),
                  tail_spec],
        out_specs=[rows_of(C), tail_spec])
    return pl.pallas_call(
        functools.partial(_kda_conv_step_kernel, taps=taps, width=C),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, C), jnp.float32),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        # operand numbering counts the prefetched scalar
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="apex_kda_conv_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), x,
      active.astype(jnp.int32)[:, None], w, tails)


def conv_step(x, w, tails, active, layer, impl="auto"):
    """One step of the short convolution a slot on the stacked per-slot
    tails, IN PLACE (a KDA layer's three convolutions side by side, or a
    Mamba-2 mixer's one; a bias is the caller's to add): the one
    dispatch between ``apex_kda_conv_step``
    (:data:`CONV_SLOTS` slots a grid step, each slot's row read, an
    active one's shifted, written back) and :func:`conv_step_xla`
    (shapes there).  A chosen kernel degrades once through the fallback
    registry ("kda_conv_step")."""
    args = (x, w, tails, active, layer)
    return _dispatch(
        "kda_conv_step", impl,
        lambda: _conv_step_pallas(*args, interpret=(impl == "interpret")),
        lambda: conv_step_xla(*args))


# ------------------------------------------------------------------ install
#: bytes a block of ``apex_slot_install`` may hold (in and out, each
#: double-buffered: four of them in VMEM)
INSTALL_BLOCK_BYTES = 2 ** 21


def _slot_install_kernel(slot_ref, new_ref, old_ref, out_ref):
    del slot_ref, old_ref
    out_ref[0, 0] = new_ref[0]


def _slot_install_row_kernel(slot_ref, new_ref, old_ref, out_ref, *, n):
    """A slot whose values are ONE row: the block of ``n`` slots that
    holds it is read, that row replaced, the block written back."""
    row = jax.lax.broadcasted_iota(jnp.int32, old_ref.shape[1:], 0)
    out_ref[0] = jnp.where(row == slot_ref[0] % n, new_ref[0], old_ref[0])


def _install_row_pallas(rows, new, slot, interpret=False):
    L, total, flat = rows.shape
    n = min(CONV_SLOTS, total)
    block = pl.BlockSpec((1, n, flat), lambda l, s: (l, s[0] // n, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(L,),
        in_specs=[pl.BlockSpec((1, 1, flat), lambda l, s: (l, 0, 0)), block],
        out_specs=block)
    return pl.pallas_call(
        functools.partial(_slot_install_row_kernel, n=n), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="apex_slot_install",
    )(jnp.asarray(slot, jnp.int32).reshape(1),
      new.astype(rows.dtype)[:, None], rows)


def _install_pallas(rows, new, slot, interpret=False):
    L = rows.shape[0]
    shape = rows.shape[2:]
    if new.shape != (L,) + shape or not shape:
        raise ValueError(f"install_rows: {new.shape} does not fit one slot "
                         f"of {rows.shape}")
    if len(shape) == 1:
        return _install_row_pallas(rows, new, slot, interpret)
    # a slot's values of one layer, cut along their first axis (only
    # where that is no tiled axis) into blocks that fit
    each = rows.dtype.itemsize
    for n in shape[1:]:
        each *= n
    cut = shape[0] if len(shape) < 3 else max(
        d for d in range(1, shape[0] + 1)
        if shape[0] % d == 0 and (d * each <= INSTALL_BLOCK_BYTES or d == 1))
    block = (cut,) + shape[1:]
    zeros = (0,) * (len(shape) - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(L, shape[0] // cut),
        in_specs=[pl.BlockSpec((1,) + block,
                               lambda l, i, s: (l, i) + zeros),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1) + block,
                               lambda l, i, s: (l, s[0], i) + zeros))
    return pl.pallas_call(
        _slot_install_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="apex_slot_install",
    )(jnp.asarray(slot, jnp.int32).reshape(1), new.astype(rows.dtype), rows)


def install_rows(rows, new, slot, impl="auto"):
    """``rows[:, slot] = new``, in place: ``rows`` (L, slots + 1, ...)
    a stacked per-slot state (a KDA or a Mamba-2 state, a convolution's
    tails), ``new`` (L, ...) one slot's values for
    every layer, ``slot`` a (traced) scalar.  The kernel
    (``apex_slot_install``) writes the slot's blocks through
    input/output aliasing and touches nothing else; the XLA form is a
    ``dynamic_update_slice``."""
    return _dispatch(
        "slot_install", impl,
        lambda: _install_pallas(rows, new, slot,
                                interpret=(impl == "interpret")),
        lambda: jax.lax.dynamic_update_slice_in_dim(
            rows, new.astype(rows.dtype)[:, None], slot, axis=1))
