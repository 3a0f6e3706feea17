"""Pallas fused-CE kernels (ops/fused_ce_pallas.py) — interpreter-mode
parity on CPU (the kernels engage for real only on TPU; see
tests/test_layer_norm_pallas.py for the same convention).

The scan path's tests (test_fused_ce.py) re-run on this path too when
APEX_TPU_FUSED_CE_PALLAS=interpret is exported; here we pin the
highest-value cases permanently: raw kernel parity, the dispatch
integration through gpt_loss, and the tp pmax/psum recombination."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.models.gpt import GPTConfig, gpt_loss, init_params
from apex_tpu.ops._pallas_tiling import LANES, VMEM_BUDGET, sublane
from apex_tpu.ops.fused_ce_pallas import (
    fused_ce_bwd_pallas,
    fused_ce_fwd_pallas,
    plan_blocks,
    table_dtype,
)


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FUSED_CE_PALLAS", "interpret")
    monkeypatch.setenv("APEX_TPU_FUSED_CE_DOT", "float32")


def _data(N=64, H=32, V=96):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, H), jnp.float32)
    e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)
    return x, e, t


def test_fwd_kernel_matches_dense():
    x, e, t = _data()
    logits = x @ e.T
    m, l, tgt = fused_ce_fwd_pallas(x, e, t, block_n=16, block_v=32,
                                    interpret=True)
    np.testing.assert_allclose(
        np.asarray(m + jnp.log(l)),
        np.asarray(jax.scipy.special.logsumexp(logits, -1)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tgt),
        np.asarray(jnp.take_along_axis(logits, t[:, None], -1)[:, 0]),
        rtol=1e-6)


@pytest.mark.parametrize("shape", [(90, 32, 393), (24, 8, 100)])
def test_edge_shapes_ceil_grid(shape):
    """Non-lane-aligned N and V (e.g. a tp8 vocab shard 6288 = 2^4·3·131
    has NO aligned divisor): the ceil-grid edge tiles must mask their
    overrun rows/cols — including zeroing garbage operand rows before
    the MXU dots (0 × NaN = NaN inside a contraction)."""
    N, H, V = shape
    x = jax.random.normal(jax.random.PRNGKey(0), (N, H), jnp.float32)
    e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (N,), 0, V)
    g = jax.random.normal(jax.random.PRNGKey(3), (N,)) / N
    logits = x @ e.T
    lse_ref = jax.scipy.special.logsumexp(logits, -1)
    m, l, tgt = fused_ce_fwd_pallas(x, e, t, block_n=64, block_v=128,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(m + jnp.log(l)),
                               np.asarray(lse_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tgt),
        np.asarray(jnp.take_along_axis(logits, t[:, None], -1)[:, 0]),
        rtol=1e-5, atol=1e-5)

    def loss(x, e):
        lg = x @ e.T
        return jnp.sum(g * (jax.scipy.special.logsumexp(lg, -1)
                            - jnp.take_along_axis(lg, t[:, None], -1)[:, 0]))

    dx_ref, de_ref = jax.grad(loss, argnums=(0, 1))(x, e)
    dx, de = fused_ce_bwd_pallas(x, e, t, lse_ref, g, block_n=64,
                                 block_v=128, interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(de), np.asarray(de_ref),
                               rtol=1e-4, atol=1e-4)


def test_bwd_kernels_match_autodiff():
    x, e, t = _data()
    g = jax.random.normal(jax.random.PRNGKey(3), (x.shape[0],))

    def loss(x, e):
        lg = x @ e.T
        ls = jax.scipy.special.logsumexp(lg, -1)
        tg = jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
        return jnp.sum(g * (ls - tg))

    dx_ref, de_ref = jax.grad(loss, argnums=(0, 1))(x, e)
    lse = jax.scipy.special.logsumexp(x @ e.T, -1)
    dx, de = fused_ce_bwd_pallas(x, e, t, lse, g, block_n=16, block_v=32,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(de), np.asarray(de_ref),
                               rtol=1e-5, atol=1e-5)


BF16, F32 = jnp.bfloat16, jnp.float32
#: (N, H, V, x dtype, table dtype as the kernels get it)
PLANNED = {
    "train-b8": (8192, 1024, 50304, BF16, BF16),
    "train-8k": (16384, 2048, 25024, BF16, BF16),
    "tp8-shard": (8192, 1024, 6288, BF16, BF16),   # no lane-aligned divisor
    "under-one-block": (90, 32, 393, BF16, BF16),
    "h2048-f32-table": (16384, 2048, 25024, BF16, F32),
    "f32-dots": (8192, 1024, 50304, F32, F32),
}


@pytest.mark.parametrize("kernel", ["fwd", "dx", "dembed"])
@pytest.mark.parametrize("shape", sorted(PLANNED))
def test_every_plan_fits_vmem_on_aligned_blocks(shape, kernel):
    """The planner sees shapes and dtypes only: whatever it picks is
    priced under 0.8 of the VMEM budget by its own pricing, a row block
    of whole sublane tiles and a vocabulary block of whole lane tiles,
    a grid that covers the arrays, and the table's bytes a call are
    what that grid streams."""
    N, H, V, xd, ed = PLANNED[shape]
    p = plan_blocks(kernel, N, H, V, xd, ed)
    assert p.vmem_bytes <= 0.8 * VMEM_BUDGET
    assert p.bn % sublane(xd) == 0 and p.bv % LANES == 0
    assert p.bv >= min(256, -(-V // LANES) * LANES) or ed == F32
    nn, nv = p.grid
    assert (nn - 1) * p.bn < N <= nn * p.bn
    assert (nv - 1) * p.bv < V <= nv * p.bv
    reads = 1 if kernel == "dembed" else nn
    assert p.table_bytes == reads * V * H * jnp.dtype(ed).itemsize
    assert p.table_dtype == ed


def test_the_train_cells_plans():
    """What the planner makes of the two training cells.  train-b8: the
    forward takes the widest vocabulary block (a row's bookkeeping is
    paid once a block: 25 blocks where 512 columns made 99) and dx the
    tallest row block, so the bf16 table is read 32 and 16 times, 3.3
    and 1.65 GB a call where the float32 master made 6.6 GB in each;
    dembed reads it once.  train-8k (hidden 2,048): the forward's
    vocabulary block doubles to 1,024, dx and dembed keep the parent's
    blocks (their accumulators leave no room)."""
    before = 32 * 50304 * 1024 * 4
    fwd, dx, de = (plan_blocks(k, *PLANNED["train-b8"])
                   for k in ("fwd", "dx", "dembed"))
    assert [p[:2] for p in (fwd, dx, de)] == [
        (256, 2048), (512, 512), (512, 512)]
    assert fwd.table_bytes == before // 2 and fwd.grid == (32, 25)
    assert dx.table_bytes == before // 4
    assert de.table_bytes == 50304 * 1024 * 2
    fwd, dx, de = (plan_blocks(k, *PLANNED["train-8k"])
                   for k in ("fwd", "dx", "dembed"))
    assert [p[:2] for p in (fwd, dx, de)] == [
        (256, 1024), (256, 512), (256, 256)]


def test_explicit_blocks_override_the_planner():
    N, H, V, xd, ed = PLANNED["train-b8"]
    for kernel in ("fwd", "dx", "dembed"):
        p = plan_blocks(kernel, N, H, V, xd, ed, block_n=256, block_v=128)
        assert (p.bn, p.bv) == (256, 128)


@pytest.mark.parametrize("embed,dot,want", [
    (F32, BF16, BF16), (BF16, BF16, BF16), (F32, F32, F32),
    (BF16, F32, BF16)])
def test_the_table_is_narrowed_only_to_the_dots_dtype(embed, dot, want):
    assert table_dtype(embed, dot) == want


def test_tall_block_with_both_edges_matches_the_scan_path():
    """The planner's own blocks where they are largest: 1,300 rows walk
    as one 1,024-row block and an edge block of 276, 2,500 vocabulary
    rows as a 2,048 block and an edge of 452 (dx: four of 512 and one
    of 452), in the SAME call: forward statistics, dx and dembed
    against the scan path's chunk functions."""
    from apex_tpu.ops.fused_ce import _chunk_grads, _chunk_stats

    N, H, V = 1300, 32, 2500
    x, e, t = _data(N, H, V)
    g = jax.random.normal(jax.random.PRNGKey(3), (N,)) / N
    # every kernel's grid ends in an edge block in both dimensions
    for kernel, blocks in (("fwd", (1024, 2048, (2, 2))),
                           ("dx", (1024, 512, (2, 5))),
                           ("dembed", (1024, 2048, (2, 2)))):
        assert plan_blocks(kernel, N, H, V, F32, F32)[:3] == blocks
    lse_ref, tgt_ref = _chunk_stats(x[:, None], e, t[:, None], None)
    dx_ref, de_ref = _chunk_grads(x[:, None], e, t[:, None], lse_ref,
                                  g[:, None], None)
    m, l, tgt = fused_ce_fwd_pallas(x, e, t, interpret=True)
    np.testing.assert_allclose(np.asarray(m + jnp.log(l)),
                               np.asarray(lse_ref[:, 0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tgt), np.asarray(tgt_ref[:, 0]),
                               rtol=1e-5, atol=1e-5)
    dx, de = fused_ce_bwd_pallas(x, e, t, lse_ref[:, 0], g, interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref[:, 0]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(de), np.asarray(de_ref),
                               rtol=1e-4, atol=1e-6)


def test_a_table_cast_once_gives_the_tile_casts_bits():
    """bf16 dots: the float32 master cast tile by tile inside the
    kernels, and the same master cast once outside them, at the same
    blocks: m, l, tgt, dx and dembed agree bit for bit (edge blocks in
    both dimensions)."""
    N, H, V = 200, 128, 700
    x, e, t = _data(N, H, V)
    x = x.astype(BF16)
    g = jax.random.normal(jax.random.PRNGKey(3), (N,)) / N
    kw = dict(dot_dtype=BF16, block_n=128, block_v=256, interpret=True)
    wide = fused_ce_fwd_pallas(x, e, t, **kw)
    narrow = fused_ce_fwd_pallas(x, e.astype(BF16), t, **kw)
    for a, b in zip(wide, narrow):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    lse = wide[0] + jnp.log(wide[1])
    wide = fused_ce_bwd_pallas(x, e, t, lse, g, **kw)
    narrow = fused_ce_bwd_pallas(x, e.astype(BF16), t, lse, g, **kw)
    assert wide[1].dtype == narrow[1].dtype == F32
    for a, b in zip(wide, narrow):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_wrapper_hands_the_kernels_a_narrow_table(monkeypatch):
    """Through ``fused_lm_head_ce``: with bf16 dots the float32 master
    reaches all three kernels as bf16 (one cast a pass, outside them),
    dembed still comes back in the master's dtype, and with float32
    dots nothing is cast."""
    from apex_tpu.ops.fused_ce import fused_lm_head_ce

    S, B, H, V = 16, 2, 32, 48
    x = jax.random.normal(jax.random.PRNGKey(0), (S, B, H), BF16)
    e = jax.random.normal(jax.random.PRNGKey(1), (V, H), F32)
    t = jax.random.randint(jax.random.PRNGKey(2), (S, B), 0, V)

    def tables(dot):
        monkeypatch.setenv("APEX_TPU_FUSED_CE_DOT", dot)
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda x, e: jnp.mean(fused_lm_head_ce(x, e, t, 8)),
            argnums=(0, 1)))(x, e)
        calls = [q for q in jaxpr.jaxpr.eqns if q.primitive.name == "pallas_call"]
        assert len(calls) == 3
        assert jaxpr.out_avals[2].dtype == F32
        return {str(q.invars[1].aval.dtype) for q in calls}

    assert tables("bfloat16") == {"bfloat16"}
    assert tables("float32") == {"float32"}


def test_the_sweep_rehearses_on_the_cpu(capsys):
    """``benchmarks/fused_ce_sweep.py --interpret``: every variant of
    every kernel gives a line with the blocks taken and a timing, the
    first of a kernel the planner's own choice (no block asked for)."""
    import importlib.util
    import json
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "fused_ce_sweep", Path(__file__).resolve().parents[1]
        / "benchmarks" / "fused_ce_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    sweep.main(["--interpret", "--shapes", "gpt", "--reps", "1"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == sum(len(v) for v in sweep.VARIANTS.values())
    assert all("ms" in l and "error" not in l for l in lines), lines
    for kernel in sweep.VARIANTS:
        first = next(l for l in lines if l["kernel"] == kernel)
        assert first["asked"] == [None, None] and first["table"] == "bfloat16"


@pytest.mark.parametrize("shape", [
    (8192, 768, 50304),   # GPT-124M head, dense
    (8192, 768, 6288),    # tp8 vocab shard (no lane-aligned divisor)
])
def test_kernels_lower_for_tpu_target(shape):
    """Cross-platform lowering (jax.export, platforms=['tpu']) runs the
    full Pallas→Mosaic path without a device: BlockSpec/layout/op
    legality errors surface HERE instead of at the kernels' hardware
    debut inside an audited bench section."""
    from jax import export as jexport

    from apex_tpu.ops import fused_ce_pallas as k

    N, H, V = shape
    x = jax.ShapeDtypeStruct((N, H), jnp.bfloat16)
    e = jax.ShapeDtypeStruct((V, H), jnp.float32)
    t = jax.ShapeDtypeStruct((N,), jnp.int32)
    lse = jax.ShapeDtypeStruct((N,), jnp.float32)
    g = jax.ShapeDtypeStruct((N,), jnp.float32)
    fwd = jexport.export(jax.jit(lambda x, e, t: k.fused_ce_fwd_pallas(x, e, t)),
                         platforms=["tpu"])(x, e, t)
    assert len(fwd.mlir_module_serialized) > 0
    bwd = jexport.export(
        jax.jit(lambda x, e, t, lse, g: k.fused_ce_bwd_pallas(x, e, t, lse, g)),
        platforms=["tpu"])(x, e, t, lse, g)
    assert len(bwd.mlir_module_serialized) > 0


def test_gpt_loss_grad_lowers_for_tpu_with_kernels(monkeypatch):
    """value_and_grad(gpt_loss) with the kernels FORCED on lowers for
    the TPU target — the CE kernels validated inside the real model
    graph (residual threading, float0 cotangent, reshapes), not just
    standalone."""
    from jax import export as jexport

    monkeypatch.setenv("APEX_TPU_FUSED_CE_PALLAS", "1")
    cfg = dataclasses.replace(CFG, compute_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    def step(params, tokens, targets):
        return jax.value_and_grad(gpt_loss)(params, tokens, targets, cfg)

    exp = jexport.export(jax.jit(step), platforms=["tpu"])(params, tok, tok)
    assert len(exp.mlir_module_serialized) > 0


def test_out_of_range_targets_match_scan_path(monkeypatch):
    """Dense-mode ids outside [0, V) must clamp IDENTICALLY on both
    impls (the scan path's take_along_axis clamps; the kernel clamps in
    _local_targets) — platform-dependent losses for the same inputs
    would be a silent correctness trap."""
    from apex_tpu.ops.fused_ce import fused_lm_head_ce

    S, B, H, V = 16, 2, 32, 48
    x = jax.random.normal(jax.random.PRNGKey(0), (S, B, H), jnp.float32)
    e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (S, B), 0, V)
    t = t.at[0, 0].set(-1).at[1, 1].set(V + 7)

    def mean_loss(x, e):
        return jnp.mean(fused_lm_head_ce(x, e, t, 8))

    got = float(mean_loss(x, e))
    got_g = jax.grad(mean_loss, argnums=(0, 1))(x, e)
    monkeypatch.setenv("APEX_TPU_FUSED_CE_PALLAS", "0")
    ref = float(mean_loss(x, e))
    ref_g = jax.grad(mean_loss, argnums=(0, 1))(x, e)
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


CFG = GPTConfig(
    vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
    max_seq_len=16, compute_dtype=jnp.float32, checkpoint_layers=False,
    fused_ce=True, fused_ce_chunk=8,
)


def test_gpt_loss_via_kernels_matches_dense():
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, size=(2, 16)))
    targets = jnp.roll(tokens, -1, axis=1)
    params = init_params(CFG, jax.random.PRNGKey(0))
    dense = dataclasses.replace(CFG, fused_ce=False)
    ref, ref_g = jax.value_and_grad(gpt_loss)(params, tokens, targets, dense)
    got, got_g = jax.value_and_grad(gpt_loss)(params, tokens, targets, CFG)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        got_g, ref_g)


def test_tp_recombination_matches_dense(devices8):
    """Kernel per shard + pmax/psum outside == global softmax: the
    (m, l, tgt) recombination is the load-bearing tp contract."""
    from apex_tpu.ops.fused_ce import fused_lm_head_ce

    S, B, H, V, tp = 16, 2, 32, 64, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (S, B, H), jnp.float32)
    e = jax.random.normal(jax.random.PRNGKey(1), (V, H), jnp.float32)
    t = jax.random.randint(jax.random.PRNGKey(2), (S, B), 0, V)

    def dense(x, e):
        lg = jnp.matmul(x, e.T)
        ls = jax.scipy.special.logsumexp(lg, -1)
        tg = jnp.take_along_axis(lg, t[..., None], -1)[..., 0]
        return jnp.mean(ls - tg)

    ref = dense(x, e)
    dx_ref, de_ref = jax.grad(dense, argnums=(0, 1))(x, e)

    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))

    def local(x, e_local):
        def f(x, e_local):
            return jnp.mean(fused_lm_head_ce(x, e_local, t, 8, "tp"))

        loss = f(x, e_local)
        dx, de = jax.grad(f, argnums=(0, 1))(x, e_local)
        return loss, jax.lax.psum(dx, "tp"), de

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(), P("tp", None)),
                      out_specs=(P(), P(), P("tp", None)),
                      check_vma=False)
    loss, dx, de = f(x, e)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(de), np.asarray(de_ref),
                               rtol=1e-5, atol=1e-6)
