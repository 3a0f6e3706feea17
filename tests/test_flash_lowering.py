"""TPU-target lowering guard for the flash attention kernels.

``jax.export`` (platforms=['tpu']) runs the full Pallas→Mosaic
lowering without a device.  The per-shape tuned-block table
(``flash_attention_pallas._TUNED_BLOCKS``) is installed from sweep
output by ``benchmarks/install_tuned_blocks.py`` — a bad entry must
fail HERE, not on the chip.  The sweep and the installer are smoke-
tested below on the CPU (interpret mode, a scratch copy of the kernel)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import export as jexport

from apex_tpu.ops import flash_attention_pallas as fap


def _lower(fn, *avals):
    exp = jexport.export(jax.jit(fn), platforms=["tpu"])(*avals)
    assert len(exp.mlir_module_serialized) > 0


@pytest.mark.parametrize("shape", [
    (8, 12, 1024, 64),    # GPT-124M attention
    (2, 12, 4096, 64),    # long-context
    (8, 8, 1024, 128),    # wide head
])
def test_fwd_lowers_for_tpu(shape):
    B, H, S, D = shape
    q = jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16)
    _lower(lambda q, k, v: fap.flash_fwd_pallas(
        q, k, v, 1.0 / D ** 0.5, True, 0, 0, heads=H), q, q, q)


def test_bwd_lowers_for_tpu():
    B, H, S, D = 8, 12, 1024, 64
    q = jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16)
    r = jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32)
    _lower(lambda q, k, v, o, lse, do: fap.flash_bwd_pallas(
        q, k, v, o, lse, do, 1.0 / D ** 0.5, True, 0, 0, heads=H),
        q, q, q, q, r, q)


def test_fused_ce_small_n_bf16_lowers_for_tpu():
    """Small-N bf16 fused-CE: the row block must round up to the bf16
    (16, 128) sublane tile, not fp32's (8, 128) — ``_ceil_block(N,
    block_n, align=8)`` on bf16 inputs was exactly the dtype-dependent
    tiling class ADVICE r5 flagged (and the static analyzer's APX302
    rule now lints for)."""
    from apex_tpu.ops import fused_ce_pallas as fcp

    assert fcp._sublane(jnp.bfloat16) == 16
    assert fcp._sublane(jnp.float32) == 8
    # N below block_n forces the ceil-rounded edge block the bug lived in
    assert fcp._ceil_block(8, 256, align=fcp._sublane(jnp.bfloat16)) == 16

    N, H, V = 8, 128, 384
    x = jax.ShapeDtypeStruct((N, H), jnp.bfloat16)
    e = jax.ShapeDtypeStruct((V, H), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((N,), jnp.int32)
    _lower(lambda x, e, t: fcp.fused_ce_fwd_pallas(x, e, t), x, e, t)
    lse = jax.ShapeDtypeStruct((N,), jnp.float32)
    _lower(lambda x, e, t, lse, g: fcp.fused_ce_bwd_pallas(x, e, t, lse, g),
           x, e, t, lse, lse)


def test_odd_seq_bf16_lowers_for_tpu():
    """Sq=40 bf16 has no 16-multiple divisor, so ``_pick_block`` keeps
    the misaligned whole-sequence block (bq=40) — pin that this shape
    still passes the Pallas→Mosaic lowering (``_pick_block`` is a
    preference, unlike fused-CE's padded ``_ceil_block`` which is a
    guarantee)."""
    B, H, S, D = 2, 2, 40, 64
    assert fap._pick_block(S, 1024, align=fap._sublane(jnp.bfloat16)) == 40
    q = jax.ShapeDtypeStruct((B * H, S, D), jnp.bfloat16)
    _lower(lambda q, k, v: fap.flash_fwd_pallas(
        q, k, v, 1.0 / D ** 0.5, True, 0, 0, heads=H), q, q, q)


def test_tuned_blocks_lower_for_tpu():
    """Whatever the sweep installed must lower for its own shape and
    phase (keys are per-phase ``(S, D, dtype, phase)``; legacy 3-element
    keys are forward entries), blocks and sub-tile as the table has
    them — and the table is not empty: it holds the three cells'
    shapes (train S 1024 D 64 both phases; the EVA window Sq 2048 D
    128; the latent prefill D 192)."""
    table = dict(fap._TUNED_BLOCKS)
    assert (1024, 64, "bfloat16", "fwd") in table
    assert (1024, 64, "bfloat16", "bwd") in table
    assert any(k[:2] == (2048, 128) for k in table)
    assert any(k[1] == 192 for k in table)
    for key, row in table.items():
        S, D, dtype = key[:3]
        phase = key[3] if len(key) == 4 else "fwd"
        q = jax.ShapeDtypeStruct((4, S, D), jnp.dtype(dtype))
        # no explicit blocks: the call reads its own row, sub-tile too
        assert fap.tuned_blocks(S, D, dtype, phase=phase) == tuple(row[:2])
        if phase == "fwd":
            _lower(lambda q, k, v: fap.flash_fwd_pallas(
                q, k, v, 1.0 / D ** 0.5, True, 0, 0, heads=4), q, q, q)
        else:
            r = jax.ShapeDtypeStruct((4, S, 1), jnp.float32)
            _lower(lambda q, k, v, o, lse, do: fap.flash_bwd_pallas(
                q, k, v, o, lse, do, 1.0 / D ** 0.5, True, 0, 0,
                heads=4), q, q, q, q, r, q)


@pytest.mark.parametrize("pooled", [0, 512, 1024])
def test_eva_window_call_lowers_for_tpu(pooled):
    """The EVA window's forward at the cell's shape: a pooled buffer
    before the window's keys (``k_offset`` < 0), a key bias, ONE key
    block over buffer and window as the caller asks, and the shape's
    sub-tile; with no buffer, one block."""
    H, W, D = 32, 2048, 128
    q = jax.ShapeDtypeStruct((H, W, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((H, W + pooled, D), jnp.bfloat16)
    if not pooled:
        _lower(lambda q, k, v: fap.flash_fwd_pallas(
            q, k, v, D ** -0.5, True, 0, 0, heads=H), q, k, k)
        return
    b = jax.ShapeDtypeStruct((1, 1, W + pooled), jnp.float32)
    _lower(lambda q, k, v, b: fap.flash_fwd_pallas(
        q, k, v, D ** -0.5, True, 0, -pooled, block_k=W + pooled,
        kv_bias=b, heads=H), q, k, k, b)


@pytest.mark.parametrize("S", [512, 4096])
def test_latent_prefill_call_lowers_for_tpu(S):
    """The latent family's prefill forward (keys 192 wide), one block
    and a grid of blocks."""
    q = jax.ShapeDtypeStruct((32, S, 192), jnp.bfloat16)
    _lower(lambda q, k, v: fap.flash_fwd_pallas(
        q, k, v, 192 ** -0.5, True, 0, 0, heads=32), q, q, q)


# ------------------------------------------- flash sweep + installer
_BENCHMARKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
FLASH_SWEEP = os.path.join(_BENCHMARKS, "flash_sweep.py")
INSTALL = os.path.join(_BENCHMARKS, "install_tuned_blocks.py")


def test_flash_sweep_quick_interpret_smoke(tmp_path):
    """``flash_sweep.py --quick --interpret`` is the CPU smoke contract:
    tiny shapes through the Pallas interpreter, one JSON line per
    config, and a final per-(shape, phase) ``tuned_blocks_table`` line
    with BOTH phases that ``set_tuned_blocks`` ingests directly."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, FLASH_SWEEP, "--quick", "--interpret"],
        capture_output=True, text=True, timeout=540, env=env,
    )
    assert proc.returncode == 0, (proc.stderr or "")[-2000:]
    table = None
    for line in (proc.stdout or "").splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "tuned_blocks_table" in rec:
            table = rec["tuned_blocks_table"]
    assert table, "no tuned_blocks_table line on stdout"
    phases = {tuple(key)[3] for key, _ in table}
    assert phases == {"fwd", "bwd"}, table
    # the printed pairs install directly, per phase
    from apex_tpu.ops import flash_attention_pallas as fap

    saved = dict(fap._TUNED_BLOCKS)
    try:
        fap._TUNED_BLOCKS.clear()
        fap.set_tuned_blocks(table)
        for key, val in table:
            s, d, dtype, phase = key
            assert fap.tuned_blocks(s, d, dtype, phase=phase) == tuple(val[:2])
            assert fap.tuned_subtile(s, d, dtype, phase=phase) == (
                val[2] if len(val) > 2 else None)
    finally:
        fap._TUNED_BLOCKS.clear()
        fap._TUNED_BLOCKS.update(saved)


def _run_installer(kernel_path, sweep_path, monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location("install_tuned_blocks",
                                                  INSTALL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from pathlib import Path

    mod.KERNEL = Path(kernel_path)
    monkeypatch.setattr(sys, "argv",
                        ["install_tuned_blocks.py", str(sweep_path),
                         "--provenance", "cpu-test 2026-08-07"])
    mod.main()


def test_install_tuned_blocks_round_trip(tmp_path, monkeypatch):
    """Installer contract: per-phase sweep keys land as 4-tuple entries,
    an old 3-tuple entry already in the source literal migrates to
    ``"fwd"`` (pre-split sweeps measured the forward path), and a
    second run with the same sweep output is BYTE-IDENTICAL
    (idempotent — re-running never churns the kernel source)."""
    import ast
    import re

    kernel = tmp_path / "kernel_stub.py"
    kernel.write_text(
        "# stub kernel module for the installer test\n"
        "_TUNED_BLOCKS: dict = {\n"
        "    (1024, 64, 'bfloat16'): (512, 256),\n"
        "}\n"
        "OTHER = 1\n")
    sweep = tmp_path / "sweep.jsonl"
    sweep.write_text(
        json.dumps({"roofline_tflops": 1.0}) + "\n" + json.dumps(
            {"tuned_blocks_table": [
                [[256, 64, "bfloat16", "fwd"], [128, 128]],
                [[256, 64, "bfloat16", "bwd"], [64, 64]],
                [[512, 64, "bfloat16"], [256, 256]],  # old flat key
            ]}) + "\n")
    _run_installer(kernel, sweep, monkeypatch)
    first = kernel.read_text()
    m = re.search(r"_TUNED_BLOCKS: dict = \{(.*?)\}", first, re.S)
    body = "\n".join(ln for ln in m.group(1).splitlines()
                     if not ln.strip().startswith("#"))
    entries = ast.literal_eval("{" + body + "}")
    assert entries == {
        (256, 64, "bfloat16", "fwd"): (128, 128),
        (256, 64, "bfloat16", "bwd"): (64, 64),
        (512, 64, "bfloat16", "fwd"): (256, 256),
        # the pre-existing flat entry migrated, not dropped
        (1024, 64, "bfloat16", "fwd"): (512, 256),
    }
    assert "OTHER = 1" in first  # the rest of the module is untouched
    # the installed table round-trips through the runtime setter
    from apex_tpu.ops import flash_attention_pallas as fap

    saved = dict(fap._TUNED_BLOCKS)
    try:
        fap._TUNED_BLOCKS.clear()
        fap.set_tuned_blocks(entries)
        import jax.numpy as jnp

        assert fap.tuned_blocks(256, 64, jnp.bfloat16, phase="bwd") == (64, 64)
        assert fap.tuned_blocks(1024, 64, jnp.bfloat16) == (512, 256)
    finally:
        fap._TUNED_BLOCKS.clear()
        fap._TUNED_BLOCKS.update(saved)
    # idempotency: same sweep output -> byte-identical file
    _run_installer(kernel, sweep, monkeypatch)
    assert kernel.read_text() == first


def test_install_tuned_blocks_rejects_bad_phase(tmp_path, monkeypatch):
    kernel = tmp_path / "kernel_stub.py"
    kernel.write_text("_TUNED_BLOCKS: dict = {}\n")
    sweep = tmp_path / "sweep.jsonl"
    sweep.write_text(json.dumps(
        {"tuned_blocks_table": [[[256, 64, "bfloat16", "backward"],
                                 [128, 128]]]}) + "\n")
    import pytest

    with pytest.raises(SystemExit, match="phase"):
        _run_installer(kernel, sweep, monkeypatch)
