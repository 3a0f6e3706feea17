"""The tile plan of the grouped matmuls
(``transformer/expert_parallel.grouped_tiling``): what it gives the
train chunk's six products and why that fits VMEM; that every program
the three MoE serving configurations reach (decode or block step and
every prefill bucket, read from ``cellbench/configs/*.json``) keeps,
letter for letter, the one tiling every call had until PR 46; and the
trainable chunk under the plan (``_gmm_trainable``: megablox's ``gmm`` /
``tgmm`` through the Pallas interpreter, each at its own tiles) against
``jax.lax.ragged_dot`` and its derivative."""

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
    the three MoE serving cells' programs run: the decode step (the
    latent families: ``max_batch`` tokens x top_k rows, the whole
    buffer), the block step (two blocks a slot, the compact walk's
    chunk) and a prefill of every bucket (the whole buffer), over the
    stacked expert layers' held experts as groups."""
    calls = []
    for stem, experts, top_k, dense in (
            ("gigachat3.1-702b-a36b-serve-ep16", "n_routed_experts",
             "num_experts_per_tok", "first_k_dense_replace"),
            ("kimi-linear-48b-a3b-serve-ep8", "num_experts",
             "num_experts_per_token", "first_k_dense_replace"),
            ("sdar-30b-a3b-serve-ep8", "num_experts",
             "num_experts_per_tok", None)):
        conf = json.loads((CONFIGS / f"{stem}.json").read_text())
        args = conf["cellbench"]["args"]
        held, k = conf[experts], conf[top_k]
        layers = conf["num_hidden_layers"] - (conf[dense] if dense else 0)
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


@pytest.mark.parametrize("rows,groups,k,n", _serving_calls())
def test_a_serving_program_keeps_its_tiling(rows, groups, k, n):
    """Rows over groups is 1.3-102 there: within a row tile or a few,
    where an expert's matrix is read once whatever the tiles are."""
    assert rows // groups < ep.RESIDENT_ROWS_A_GROUP // 4
    assert ep.grouped_tiling("gmm", rows, groups, k, n, BF16) \
        == _before_pr46(rows, k, n)


def test_the_serving_calls_are_the_ones_the_issue_counted():
    ids = [p.id for p in _serving_calls()]
    assert len(ids) == 2 * (4 + 5 + 5)
    shapes = {p.id: p.values[:2] for p in _serving_calls()}
    assert shapes["gigachat3.1-decode_step-down"] == (1024, 80)
    assert shapes["gigachat3.1-prefill1024-down"] == (8192, 80)
    assert shapes["kimi-prefill4096-gate_up"] == (32768, 384)
    assert shapes["sdar-block_step-gate_up"] == (1024, 768)
    assert shapes["sdar-prefill768-gate_up"] == (6144, 768)


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


def test_the_sweep_rehearses_on_the_cpu(capsys):
    """``benchmarks/grouped_matmul_sweep.py --interpret``: a line a row
    tile of the metadata alone, then a line a product and candidate,
    each timed on both draws; the skewed draw has its empty group and
    its fourfold one, and both leave the chunk's tail dead."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "grouped_matmul_sweep", CONFIGS.parents[1] / "benchmarks"
        / "grouped_matmul_sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
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
