"""The tile plan of the grouped matmuls
(``transformer/expert_parallel.grouped_tiling``): what it gives the
train chunk's six products and why that fits VMEM; what it gives every
program the four MoE serving configurations reach (decode or block step
and every prefill bucket, read from ``cellbench/configs/*.json``): a
contraction in ONE tile wherever that fits, and the one stated
exception; a serving call under the plan through the Pallas interpreter
(groups that straddle a row tile's edge, an empty one, one that ends on
an edge, dead rows behind; a contraction 1,024 does not divide); and the
trainable chunk under the plan (``_gmm_trainable``: megablox's ``gmm`` /
``tgmm``, each at its own tiles) against ``jax.lax.ragged_dot`` and its
derivative."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer import expert_parallel as ep

CONFIGS = Path(__file__).resolve().parents[1] / "cellbench" / "configs"
BF16, F32 = jnp.bfloat16, jnp.float32
MIB = 2 ** 20

# ------------------------------------------------------ the train chunk
#: trinity-mini.train-8k: 2 x 8,192 tokens, top_k 8, 16 of 128 experts
#: held -> a chunk of 20,480 rows, 1,280 a group; experts of 2,048 x 1,024
ROWS, GROUPS, HIDDEN, WIDTH = 20480, 16, 2048, 1024
TRAIN = {
    # product, (contraction, columns): tiling, MiB by the docstring's sum
    "gate_up": ("gmm", HIDDEN, WIDTH, (256, 2048, 1024), 12.0),
    "down": ("gmm", WIDTH, HIDDEN, (256, 1024, 2048), 13.0),
    "d_act": ("gmm_t", HIDDEN, WIDTH, (256, 2048, 1024), 12.0),
    "d_rows": ("gmm_t", WIDTH, HIDDEN, (256, 1024, 2048), 13.0),
    "tgmm_gate_up": ("tgmm", HIDDEN, WIDTH, (128, 1024, 1024), 9.0),
    "tgmm_down": ("tgmm", WIDTH, HIDDEN, (128, 1024, 1024), 9.0),
}


def test_the_chunk_is_the_train_cells():
    conf = json.loads(
        (CONFIGS / "trinity-mini-26b-a3b-train-ep8.json").read_text())
    mix = json.loads((CONFIGS.parent / "traffic" / "steady-8k.json")
                     .read_text())
    tokens = mix["global_batch"] * conf["cellbench"]["args"]["seq"]
    assert ep.expert_buffer_rows(
        tokens, conf["num_experts_per_tok"], conf["num_experts"],
        conf["published"]["num_experts"]) == ROWS
    assert (conf["num_experts"], conf["hidden_size"],
            conf["moe_intermediate_size"]) == (GROUPS, HIDDEN, WIDTH)


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_the_train_chunks_products_keep_an_experts_block_in_vmem(name):
    """A ``gmm`` contracts in one tile and takes all the columns, so its
    weight block's index ``(group, 0, 0)`` stands for all of a group's
    row tiles and the rows are read once; a ``tgmm`` keeps the tiles it
    had; all of it under the scoped VMEM limit by the arithmetic the
    docstring states."""
    product, k, n, want, mib = TRAIN[name]
    tiling = ep.grouped_tiling(product, ROWS, GROUPS, k, n, BF16)
    assert tiling == want
    tm, tk, tn = tiling
    assert ROWS % tm == 0
    if product == "tgmm":
        assert tiling == _before_pr46(ROWS, k, n)
        blocks = 2 * 2 * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    else:
        assert (tk, tn) == (k, n)       # ONE contraction tile, one column
        blocks = 2 * 2 * (tm * tk + tm * tn + tk * tn) + 4 * tm * tn
    assert ep.grouped_vmem_bytes(product, tiling, 2) == blocks == mib * MIB
    assert blocks <= ep.SCOPED_VMEM_BYTES == 16 * MIB


def test_a_float32_chunk_halves_its_columns_to_fit():
    """The same chunk in float32: a resident block of 2,048 x 1,024
    takes 8 MiB a buffer, so the columns halve (at 128 rows this sum
    says 19.5 MiB, and Mosaic refused that block for 19.00), and the
    down projection's 2,048 columns halve too."""
    assert ep.grouped_vmem_bytes("gmm", (128, 2048, 1024), 4) == 19.5 * MIB
    assert ep.grouped_tiling("gmm", ROWS, GROUPS, HIDDEN, WIDTH, F32) \
        == (256, 2048, 512)
    assert ep.grouped_tiling("gmm_t", ROWS, GROUPS, WIDTH, HIDDEN, F32) \
        == (256, 1024, 1024)
    assert ep.grouped_tiling("tgmm", ROWS, GROUPS, HIDDEN, WIDTH, F32) \
        == (128, 1024, 1024)


def test_a_chunk_256_does_not_divide_keeps_the_row_tile():
    assert ep.grouped_tiling("gmm", ROWS + 128, GROUPS, HIDDEN, WIDTH,
                             BF16) == (128, 2048, 1024)


# ------------------------------------------- the serving programs' calls
def _before_pr46(rows, k, n):
    """The tiling ``_grouped_matmul`` gave every call until PR 46."""
    tm = next(t for t in (128, 64, 32, 16, 8) if rows % t == 0)
    return tm, min(1024, k), min(1024, n)


def _serving_calls():
    """(id, rows, groups, contraction, columns) of every grouped matmul
    the four MoE serving cells' programs run: the decode step (the
    latent families and the hybrid: ``max_batch`` tokens x top_k rows,
    the whole buffer), the block step (two blocks a slot, the compact
    walk's chunk) and a prefill of every bucket (the whole buffer), over
    the stacked expert layers' held experts as groups (the hybrid's: the
    repeats of its period, a position's experts side by side)."""
    from apex_tpu.models.lfm2_moe import LFM2MoEConfig

    after_dense = lambda conf: (conf["num_hidden_layers"]
                                - conf["first_k_dense_replace"])
    calls = []
    for stem, experts, top_k, stacked in (
            ("gigachat3.1-702b-a36b-serve-ep16", "n_routed_experts",
             "num_experts_per_tok", after_dense),
            ("kimi-linear-48b-a3b-serve-ep8", "num_experts",
             "num_experts_per_token", after_dense),
            ("sdar-30b-a3b-serve-ep8", "num_experts",
             "num_experts_per_tok", lambda conf: conf["num_hidden_layers"]),
            ("lfm2-8b-a1b-serve-pp2", "num_experts", "num_experts_per_tok",
             lambda conf: LFM2MoEConfig.from_published(conf).plan[2])):
        conf = json.loads((CONFIGS / f"{stem}.json").read_text())
        args = conf["cellbench"]["args"]
        held, k = conf[experts], conf[top_k]
        layers = stacked(conf)
        groups = layers * held
        H, F = conf["hidden_size"], conf["moe_intermediate_size"]
        programs = {f"prefill{b}": b * k for b in sorted(
            set(args["prefill_buckets"]) | {args["max_prompt_len"]})}
        if "block_length" in args:
            tokens = args["max_batch"] * 2 * args["block_length"]
            programs["block_step"] = ep.expert_buffer_rows(
                tokens, k, held, conf["published"]["num_experts"],
                multiple=min(512, tokens * k))
        else:
            programs["decode_step"] = args["max_batch"] * k
        for program, rows in programs.items():
            for proj, (kk, nn) in (("gate_up", (H, F)), ("down", (F, H))):
                calls.append(pytest.param(
                    rows, groups, kk, nn,
                    id=f"{stem.split('-')[0]}-{program}-{proj}"))
    return calls


#: (contraction, columns) of an expert's matrix: the tiling the plan
#: gives every serving call over it (rows a multiple of 128)
SERVING = {
    (2048, 1792): (128, 2048, 896),     # LFM2 gate, up
    (1792, 2048): (128, 1792, 1024),    # LFM2 down
    (2048, 768): (128, 2048, 768),      # SDAR gate, up
    (768, 2048): (128, 768, 2048),      # SDAR down
    (2304, 1024): (128, 2304, 1024),    # Kimi gate, up: no remainder tile
    (1024, 2304): (128, 1024, 2304),    # Kimi down
    (7168, 2048): (128, 1024, 2048),    # GigaChat gate, up: THE exception
    (2048, 7168): (128, 2048, 1024),    # GigaChat down
}


@pytest.mark.parametrize("rows,groups,k,n", _serving_calls())
def test_a_serving_program_contracts_in_one_tile_where_it_fits(
        rows, groups, k, n):
    """Rows over groups is 1.3-102 there, under
    :data:`RESIDENT_ROWS_A_GROUP`: the row tile stays 128.  The
    contraction is ONE tile, so the weight block's index moves once a
    group and the rows' once a row tile, in even column tiles that fit
    the scoped VMEM limit; the stated exception: beside GigaChat's
    7,168-deep contraction no column tile of 512 fits, so it stays in
    tiles of 1,024 under all the columns at once."""
    assert rows // groups < ep.RESIDENT_ROWS_A_GROUP // 4
    tiling = ep.grouped_tiling("gmm", rows, groups, k, n, BF16)
    assert tiling == SERVING[k, n]
    tm, tk, tn = tiling
    if tk != k:
        assert (tk, tn) == (_before_pr46(rows, k, n)[1], n)
        assert ep.grouped_vmem_bytes("gmm", (tm, k, 512), 2) \
            > ep.SCOPED_VMEM_BYTES * 15 // 16
    assert tm == 128 and tn % 128 == 0 and n % tn == 0
    assert ep.grouped_vmem_bytes("gmm", tiling, 2) \
        <= ep.SCOPED_VMEM_BYTES * 15 // 16


def test_the_serving_calls_are_the_ones_the_issue_counted():
    ids = [p.id for p in _serving_calls()]
    assert len(ids) == 2 * (4 + 5 + 5 + 5)
    shapes = {p.id: p.values[:2] for p in _serving_calls()}
    assert shapes["gigachat3.1-decode_step-down"] == (1024, 80)
    assert shapes["gigachat3.1-prefill1024-down"] == (8192, 80)
    assert shapes["kimi-prefill4096-gate_up"] == (32768, 384)
    assert shapes["sdar-block_step-gate_up"] == (1024, 768)
    assert shapes["sdar-prefill768-gate_up"] == (6144, 768)
    assert shapes["lfm2-decode_step-gate_up"] == (1024, 96)
    assert shapes["lfm2-prefill1024-down"] == (4096, 96)
    assert {p.values[2:] for p in _serving_calls()} == set(SERVING)


#: 512 rows over 8 stacked groups, the second layer's four live: group
#: 2 has rows 0-100, group 3 rows 100-160 (over the edge at 128), group
#: 4 none, group 5 rows 160-256 (it ENDS on an edge), group 6 rows
#: 256-456 (over the edge at 384); the last 56 rows are dead
SERVED = (0, 0, 100, 60, 0, 96, 200, 0)


@pytest.mark.parametrize("dtype,tol,k", [
    (BF16, 2e-2, 2048), (F32, 2e-5, 2048), (BF16, 2e-2, 1024 + 256)],
    ids=["bfloat16", "float32", "a_contraction_1024_does_not_divide"])
def test_a_serving_call_under_the_plan(dtype, tol, k):
    """``_grouped_matmul`` as the serving programs call it, through
    megablox's kernel at the plan's tiles (the contraction in one tile
    of 2,048 where every call had two of 1,024, or of 1,280, Kimi's
    2,304 cut for the CPU, where the second had a remainder to mask),
    against ``ragged_dot`` on the live rows; what it leaves in the dead
    rows goes under the caller's mask."""
    rows, n, live = 512, 256, sum(SERVED)
    sizes = jnp.asarray(SERVED, jnp.int32)
    ends = np.cumsum(SERVED)
    assert [g for g, (size, end) in enumerate(zip(SERVED, ends))
            if size and (end - size) // 128 != (end - 1) // 128] == [3, 6]
    assert ends[5] % 128 == 0 and live < rows
    plan = ep.grouped_tiling("gmm", rows, len(SERVED), k, n, dtype)
    assert plan == (128, k, n) != _before_pr46(rows, k, n)
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    w = (jax.random.normal(keys[1], (len(SERVED), k, n))
         * k ** -0.5).astype(dtype)
    got = ep._grouped_matmul(x, w, sizes, "interpret")
    want = jax.lax.ragged_dot(x, w, sizes)
    assert got.dtype == want.dtype == dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(got[:live], want[:live],
                               atol=tol * np.abs(want).max())
    # the caller REPLACES the dead rows: whatever is there reaches nothing
    masked = jnp.where((jnp.arange(rows) < live)[:, None], got, 0)
    assert np.isfinite(np.asarray(masked)).all()


def test_rows_no_tile_divides_go_to_ragged_dot():
    assert ep.grouped_tiling("gmm", 1001, 4, 256, 256, BF16) is None
    assert ep.grouped_tiling("gmm", 1000, 4, 256, 256, BF16) \
        == (8, 256, 256)


# --------------------------------------------- the chunk under the plan
#: 2,048 rows over 4 groups (512 a group: the plan's resident branch):
#: group 0 spans two and three quarters row tiles of 256, every edge
#: falls inside a tile, group 1 is empty, the last 531 rows are dead
SIZES = (700, 0, 517, 300)
M, H, F = 2048, 2048, 256


def _chunk_loss(impl, dtype):
    sizes = jnp.asarray(SIZES, jnp.int32)
    valid = jnp.arange(M) < sum(SIZES)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    rows = jax.random.normal(keys[0], (M, H)).astype(dtype)
    w = [(jax.random.normal(k, s) * s[1] ** -0.5).astype(dtype)
         for k, s in zip(keys[1:4], ((len(SIZES), H, F), (len(SIZES), H, F),
                                     (len(SIZES), F, H)))]
    g = jax.random.normal(keys[4], (M, H), F32)

    def loss(rows, wg, wu, wd):
        y = ep._chunk_ffn(rows, valid, sizes, wg, wu, wd, impl)
        return jnp.sum(y.astype(F32) * g), y

    (_, y), grads = jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
        rows, *w)
    return (y,) + grads


@pytest.mark.parametrize("dtype,tol", [(BF16, 2e-2), (F32, 2e-5)],
                         ids=["bfloat16", "float32"])
def test_the_chunk_and_its_four_gradients_under_the_plan(dtype, tol):
    """``_chunk_ffn`` through megablox's kernels at the plan's tiles
    (a resident contraction of 2,048 in the gate, the up and the
    activation's cotangent, all 2,048 columns at once in the down
    projection and the rows' cotangent, a row tile of 256; ``tgmm`` at
    the tiles it had) against ``ragged_dot`` and its own derivative."""
    plan = lambda product, k, n: ep.grouped_tiling(
        product, M, len(SIZES), k, n, dtype)
    assert plan("gmm", H, F) == plan("gmm_t", H, F) == (256, H, F)
    assert plan("gmm", F, H) == plan("gmm_t", F, H) == (256, F, H)
    assert plan("gmm", H, F) != _before_pr46(M, H, F)
    assert plan("tgmm", H, F) == _before_pr46(M, H, F)
    got = _chunk_loss("interpret", dtype)
    want = _chunk_loss("xla", dtype)
    for name, a, b in zip(("y", "drows", "dgate", "dup", "ddown"), got,
                          want):
        assert a.dtype == b.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(),
                                   err_msg=name)
    # dead rows and the empty group: nothing written, nothing left
    live = sum(SIZES)
    assert not np.asarray(got[0][live:], np.float32).any()
    assert not np.asarray(got[1][live:], np.float32).any()
    for dw in got[2:]:
        assert not np.asarray(dw[1], np.float32).any()


def _sweep():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "grouped_matmul_sweep", CONFIGS.parents[1] / "benchmarks"
        / "grouped_matmul_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    return sweep


def test_the_sweep_counts_what_a_tiling_fetches():
    """``traffic`` walks megablox's grid by its block indices.  Five
    groups over four row tiles of 128, two of them over an edge: six
    visits.  With the contraction in two tiles every grid step fetches
    the rows and the weights again; in one tile each row tile and each
    live group's matrix is fetched once, which is what is owed."""
    sweep = _sweep()
    sizes, K, N = np.asarray((100, 60, 0, 96, 200)), 256, 128
    owed = 4 * K * N + 4 * 128 * (K + N)
    two = sweep.traffic(sizes, (128, 128, 128), K, N)
    one = sweep.traffic(sizes, (128, 256, 128), K, N)
    assert (two["visits"], two["straddling"]) == (6, 2) \
        == (one["visits"], one["straddling"])
    assert two["mb_owed"] == one["mb_owed"] == one["mb_moved"] \
        == round(owed * 2 / 1e6, 2)
    steps = 6 * 2
    assert two["mb_moved"] == round(
        (steps * 128 * 128 * 2 + 4 * 128 * N) * 2 / 1e6, 2)
    # the LFM2 decode step's gate call as ISSUE 48 counted it
    sizes = np.asarray(sweep.draw("serving", 1024, 96, 0, 32, 31.4))
    assert sizes.sum() == 1005 and (sizes > 0).sum() == 32 \
        and not sizes[:32].any() and not sizes[64:].any()
    parent = sweep.traffic(sizes, (128, 1024, 1024), 2048, 1792)
    plan = sweep.traffic(sizes, ep.grouped_tiling(
        "gmm", 1024, 96, 2048, 1792, BF16), 2048, 1792)
    assert (parent["visits"], parent["straddling"]) == (39, 7)
    assert plan["mb_moved"] < 1.02 * plan["mb_owed"] \
        < parent["mb_moved"] / 1.3


def test_the_sweep_rehearses_a_serving_call(capsys):
    sweep = _sweep()
    sweep.main(["--interpret", "--products", "gate_up", "--draws",
                "serving", "--live-groups", "2", "--rows-a-group", "70"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["product"] for l in lines] == ["metadata"] * 2 + ["gate_up"] * 2
    for line in lines[2:]:
        assert "error" not in line, line
        assert {"ms_serving", "visits_serving", "straddling_serving",
                "mb_owed_serving", "mb_moved_serving",
                "hbm_share_serving"} <= set(line)
    # 140 rows over two live groups: a row tile of 32 is visited by both
    assert lines[3]["visits_serving"] == 6 \
        and lines[3]["straddling_serving"] == 4


def test_the_sweep_rehearses_on_the_cpu(capsys):
    """``benchmarks/grouped_matmul_sweep.py --interpret``: a line a row
    tile of the metadata alone, then a line a product and candidate,
    each timed on both draws; the skewed draw has its empty group and
    its fourfold one, and both leave the chunk's tail dead."""
    sweep = _sweep()
    sweep.main(["--interpret", "--products", "gate_up", "tgmm_down"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["product"] for l in lines] == \
        ["metadata"] * 2 + ["gate_up"] * 2 + ["tgmm_down"] * 2
    assert all("ms_even" in l and "ms_skewed" in l and "error" not in l
               for l in lines), lines
    assert [l["parent"] for l in lines[2:]] == [True, False] * 2
    assert all("plan" in l for l in lines[2:])
    assert int(ROWS * sweep.FILL) // GROUPS == 1012
    for rows, groups in ((ROWS, GROUPS), (512, 4)):
        even, skewed = (np.asarray(sweep.draw(name, rows, groups, 0))
                        for name in ("even", "skewed"))
        mean = int(rows * sweep.FILL) // groups
        assert abs(even - mean).max() <= mean // 128
        assert skewed[0] == 0 and skewed[1] == 4 * mean
        assert max(even.sum(), skewed.sum()) < rows
