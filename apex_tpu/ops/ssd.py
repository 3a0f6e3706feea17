"""The Mamba-2 state-space recurrence (SSD: a scalar decay a head), as a
recurrent state.

A head of size ``P`` keeps ``S`` (``P x N``, float32; ``N`` the state
size).  The heads come in ``G`` groups; the heads of a group share the
input map ``B`` and the output map ``C`` (``N`` wide each, a token).  A
token with input ``x`` (``P``), step ``dt > 0`` (one a head, after its
softplus) and the head's constant ``A < 0`` does::

    S = exp(dt A) S + (dt x) B^T
    y = S C + D x

Nothing grows with the context: ``S`` is all that a sequence carries.
The second recurrence of this package beside :mod:`apex_tpu.ops.kda`
(a delta rule with a decay a key channel), and the simpler of the two:
the decay is one scalar a head and the update has no solve.  Three
forms, all float32:

- :func:`ssd_recurrent` — token by token under ``lax.scan``: the
  numerics specification of the other two;
- :func:`ssd_chunked` — the prompt, in chunks of ``chunk`` positions
  (``mamba_chunk_size``): inside a chunk the decay-masked ``C B^T``
  product a GROUP and its product with ``dt x`` a head, between chunks
  the carried state.  ONE ``lax.scan`` over the chunks holds both
  halves, so in a device trace that loop is the one operation that
  names the whole of the chunked scan (plain XLA: every product a
  matmul at ``highest`` precision).  A position with ``dt = 0`` decays
  nothing and writes nothing: that is how a caller marks padding, and
  the state handed back is the state at the last real position;
- :func:`ssd_decode` — one token a slot on the stacked per-slot state
  ``(layers, slots + 1, H, P, N)``, in place: the kernel
  ``apex_ssd_decode`` reads an ACTIVE slot's state, updates it and
  writes it back through input/output aliasing; an inactive slot's grid
  steps go to the garbage row (the last), so its own state is neither
  read nor written (as ``apex_kda_decode``).

**Decays without overflow.**  With ``L`` the running sum of ``dt A``
inside a chunk, every factor is ``exp(L_i - L_j)`` for ``j <= i``, or
``exp(L_end - L_j)``: a difference taken BEFORE the exponential and
never above 0, so nothing is factored into a part that could overflow.

The short convolution in front of the recurrence and the install of a
prefill's final state into a slot's rows are :func:`apex_tpu.ops.kda
.conv_step` and :func:`apex_tpu.ops.kda.install_rows`, which serve both
recurrences.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["CHUNK", "DECODE_HEADS", "ssd_chunked", "ssd_decode",
           "ssd_decode_xla", "ssd_recurrent"]

#: positions a chunk of :func:`ssd_chunked` holds unless told otherwise
CHUNK = 128
_HI = jax.lax.Precision.HIGHEST
_f32 = lambda x: x.astype(jnp.float32)


def _grouped(x, groups):
    """A state (..., H, P, N) as (..., G, H // G, P, N)."""
    H = x.shape[-3]
    return x.reshape(x.shape[:-3] + (groups, H // groups) + x.shape[-2:])


# --------------------------------------------------------------- recurrence
def _step(S, x, dt, A, B, C):
    """One token: ``S`` (..., G, K, P, N); ``x`` (..., G, K, P); ``dt``
    (..., G, K); ``A`` (G, K); ``B``, ``C`` (..., G, N).  Returns the
    new state and ``S C``."""
    S = S * jnp.exp(dt * A)[..., None, None] \
        + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    return S, jnp.sum(S * C[..., None, None, :], axis=-1)


def ssd_recurrent(x, dt, A, B, C, D, state):
    """The recurrence, token by token.  ``x``: (T, H, P); ``dt``: (T,
    H), positive (0: the position leaves the state untouched); ``A``:
    (H,), negative; ``B``, ``C``: (T, G, N); ``D``: (H,); ``state``:
    (H, P, N).  Returns ``(y (T, H, P), state)``, float32."""
    T, H, P = x.shape
    G = B.shape[1]
    Ag = _f32(A).reshape(G, H // G)

    def body(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S, y = _step(S, x_t, dt_t, Ag, B_t, C_t)
        return S, y

    S, y = jax.lax.scan(
        body, _grouped(_f32(state), G),
        (_f32(x).reshape(T, G, H // G, P), _f32(dt).reshape(T, G, H // G),
         _f32(B), _f32(C)))
    return y.reshape(T, H, P) + _f32(D)[None, :, None] * _f32(x), \
        S.reshape(state.shape)


# ------------------------------------------------------------------ chunked
def ssd_chunked(x, dt, A, B, C, D, state, chunk=CHUNK):
    """:func:`ssd_recurrent` of a whole sequence, chunk by chunk (module
    doc).  Shapes as there; ``T`` is padded to a multiple of ``chunk``
    with positions of ``dt = 0``.  Returns ``(y (T, H, P), state)``,
    float32."""
    T, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    K, Q = H // G, int(chunk)
    pad = -T % Q
    n = (T + pad) // Q

    def chunks(t):
        t = jnp.pad(_f32(t), ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((n, Q) + t.shape[1:])

    Ag = _f32(A).reshape(G, K)
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    mm = functools.partial(jnp.einsum, precision=_HI)

    def body(S, inp):
        x_c, dt_c, B_c, C_c = inp           # (Q,G,K,P) (Q,G,K) (Q,G,N) x2
        L = jnp.cumsum(dt_c * Ag, axis=0)                       # (Q,G,K)
        u = dt_c[..., None] * x_c                               # (Q,G,K,P)
        # inside the chunk: exp(L_i - L_j) C_i.B_j for j <= i
        cb = mm("ign,jgn->gij", C_c, B_c)                       # (G,Q,Q)
        diff = L[:, None] - L[None, :]                          # (i,j,G,K)
        decay = jnp.exp(jnp.where(lower[:, :, None, None], diff, -jnp.inf))
        M = cb[:, None] * jnp.moveaxis(decay, (0, 1), (2, 3))   # (G,K,i,j)
        y = mm("gkij,jgkp->igkp", M, u)
        # what the carried state adds, decayed from the chunk's start
        y = y + mm("ign,gkpn->igkp", C_c, S) * jnp.exp(L)[..., None]
        # the state at the chunk's end
        to_end = jnp.exp(L[-1][None] - L)                       # (Q,G,K)
        S = S * jnp.exp(L[-1])[..., None, None] \
            + mm("jgkp,jgn->gkpn", u * to_end[..., None], B_c)
        return S, y

    S, y = jax.lax.scan(
        body, _grouped(_f32(state), G),
        (chunks(x).reshape(n, Q, G, K, P), chunks(dt).reshape(n, Q, G, K),
         chunks(B), chunks(C)))
    y = y.reshape(n * Q, H, P)[:T]
    return y + _f32(D)[None, :, None] * _f32(x), S.reshape(state.shape)


# ------------------------------------------------------------------- decode
def ssd_decode_xla(x, dt, A, B, C, D, state, active, layer):
    """One token a slot on the stacked state, in XLA (the CPU path and
    the numerics specification; on the chip the update would copy the
    stacked state).  ``x``: (slots, H, P); ``dt``: (slots, H); ``A``,
    ``D``: (H,); ``B``, ``C``: (slots, G, N); ``state``: (L, slots + 1,
    H, P, N) float32; ``active``: (slots,) bool; ``layer``: scalar.
    Returns ``(y (slots, H, P), state)``: an inactive slot's row comes
    out 0 and its state as it was."""
    n, H, P = x.shape
    G = B.shape[1]
    S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new, y = _step(_grouped(S[:n], G), _f32(x).reshape(n, G, H // G, P),
                   _f32(dt).reshape(n, G, H // G),
                   _f32(A).reshape(G, H // G), _f32(B), _f32(C))
    y = y.reshape(n, H, P) + _f32(D)[None, :, None] * _f32(x)
    keep = active[:, None, None, None]
    S = S.at[:n].set(jnp.where(keep, new.reshape(S[:n].shape), S[:n]))
    state = jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)
    return jnp.where(active[:, None, None], y, 0.0), state


#: heads a grid step of ``apex_ssd_decode`` holds: 8 x (128 x 256)
#: float32 is 1 MB a block, in and out and double-buffered 4 MB
DECODE_HEADS = 8
_TILE = 128


def _ssd_decode_kernel(layer_ref, row_ref, live_ref, rows_ref, bc_ref, s_ref,
                       y_ref, out_ref, *, heads):
    """One (slot, block of heads of one group) a grid step.
    ``rows_ref`` holds the block's ``dt x`` and, under them, its decays
    (a constant row a head) as ROWS; padded to a whole tile and
    transposed once they are the columns that scale the state's rows.
    ``bc_ref``: the group's ``B`` (row 0) and ``C`` (row 1)."""
    del layer_ref, row_ref   # consumed by the index maps
    live = live_ref[pl.program_id(0)] != 0
    rows = rows_ref[0, 0]                                   # (2 heads, P)
    P = rows.shape[1]
    cols = jnp.concatenate(
        [rows, jnp.zeros((_TILE - 2 * heads, P), jnp.float32)], axis=0).T
    b_row, c_row = bc_ref[0, 0, 0:1], bc_ref[0, 0, 1:2]     # (1, N) each
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, _TILE), 1)
    ys = jnp.zeros((P, _TILE), jnp.float32)
    for h in range(heads):
        u, decay = cols[:, h:h + 1], cols[:, heads + h:heads + h + 1]
        S = s_ref[0, 0, h] * decay + u * b_row              # (P, N)
        out_ref[0, 0, h] = jnp.where(live, S, 0.0)
        ys = jnp.where(lane == h,
                       jnp.sum(S * c_row, axis=1, keepdims=True), ys)
    y_ref[0] = jnp.where(live, ys.T[:heads], 0.0)


def _decode_pallas(x, dt, A, B, C, D, state, active, layer, interpret=False):
    n, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    L, rows = state.shape[:2]
    per = H // G
    hb = next(d for d in range(min(DECODE_HEADS, per), 0, -1) if per % d == 0)
    if rows != n + 1 or state.shape[2:] != (H, P, N):
        raise ValueError(
            f"apex_ssd_decode: state {state.shape} for {n} slots of {H} "
            f"heads of {P} x {N}: needs (layers, slots + 1, H, P, N) (the "
            f"last row is the garbage row)")
    if P > _TILE or 2 * hb > _TILE:
        raise ValueError(f"apex_ssd_decode: a head of {P} rows does not "
                         f"fit one {_TILE}-row tile")
    dt, x32 = _f32(dt), _f32(x)
    decay = jnp.exp(dt * _f32(A)[None])                         # (n, H)
    blocks = lambda t: t.reshape(n, H // hb, hb, P)
    tile = jnp.concatenate(
        [blocks(dt[..., None] * x32),
         blocks(jnp.broadcast_to(decay[..., None], (n, H, P)))], axis=2)
    bc = jnp.pad(jnp.stack([_f32(B), _f32(C)], axis=2),
                 ((0, 0), (0, 0), (0, 6), (0, 0)))              # (n,G,8,N)
    live = active.astype(jnp.int32)
    row = jnp.where(active, jnp.arange(n, dtype=jnp.int32), n)
    state_spec = pl.BlockSpec(
        (1, 1, hb, P, N),
        lambda b, j, layer_ref, row_ref, live_ref: (
            layer_ref[0], row_ref[b], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n, H // hb),
        in_specs=[
            pl.BlockSpec((1, 1, 2 * hb, P), lambda b, j, *_: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, 8, N),
                         lambda b, j, *_: (b, j * hb // per, 0, 0)),
            state_spec],
        out_specs=[pl.BlockSpec((1, hb, P), lambda b, j, *_: (b, j, 0)),
                   state_spec])
    y, state = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand numbering counts the three prefetched scalars
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="apex_ssd_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row, live, tile, bc, state)
    return y + jnp.where(active[:, None, None],
                         _f32(D)[None, :, None] * x32, 0.0), state


def ssd_decode(x, dt, A, B, C, D, state, active, layer, impl="auto"):
    """One token a slot on the stacked per-slot state, IN PLACE: the
    one dispatch between ``apex_ssd_decode`` and :func:`ssd_decode_xla`
    (shapes there).  A chosen kernel degrades once through the fallback
    registry ("ssd_decode")."""
    from apex_tpu.ops.decode_attention_pallas import dispatch_kernel
    from apex_tpu.utils.platform import on_tpu

    args = (x, dt, A, B, C, D, state, active, layer)
    return dispatch_kernel(
        "ssd_decode", impl, on_tpu,
        lambda: _decode_pallas(*args, interpret=(impl == "interpret")),
        lambda: ssd_decode_xla(*args))
