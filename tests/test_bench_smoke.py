"""bench.py --smoke rides tier-1: every bench section's step fn must
still trace and compile on the CPU mesh, so bench bitrot (an API the
bench calls that a refactor moved, a step that no longer traces) is
caught here instead of on scarce chip time.  The smoke run executes
each section once at a tiny config — ~30-60 s total on this box, most
of it amortized by the persistent compile cache across runs."""

import json
import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def test_bench_smoke_all_sections_build():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, BENCH, "--smoke"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    report = None
    for line in reversed((proc.stdout or "").splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "smoke" in rec:
            report = rec
            break
    assert report is not None, (
        f"no smoke JSON on stdout; rc={proc.returncode}\n"
        f"stderr tail: {(proc.stderr or '')[-2000:]}")
    broken = {k: v for k, v in report["sections"].items()
              if not v.get("ok")}
    assert proc.returncode == 0 and not broken, (
        f"bench sections no longer build: {json.dumps(broken, indent=2)}")


def test_elastic_resume_smoke_resharded():
    """The ``elastic_resume`` bench section under a TWO-device host
    platform, isolated via ``--smoke-only``: save at dp=2, restore
    resharded at dp=1 — the section itself asserts the banded loss
    continuation (and the bitwise branch at equal worlds), so ``ok``
    means the reshard path held."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, BENCH, "--smoke", "--smoke-only", "elastic_resume"],
        capture_output=True, text=True, timeout=400, env=env,
    )
    report = None
    for line in reversed((proc.stdout or "").splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "smoke" in rec:
            report = rec
            break
    assert report is not None, (
        f"no smoke JSON; rc={proc.returncode}\n"
        f"stderr tail: {(proc.stderr or '')[-2000:]}")
    assert proc.returncode == 0 and \
        report["sections"]["elastic_resume"].get("ok"), report
    assert list(report["sections"]) == ["elastic_resume"]


def test_zero_wire_bytes_accounting_ratios():
    """The ``zero_gpt124`` section's ``wire_bytes_per_step`` field,
    validated at the accounting level (pure plan arithmetic, no step
    compile) — EXACT ratios, scale-vector bytes included per hop
    (never the old payload approximation): an int8 wire carries
    ``1 + 4/QBLOCK`` bytes per element (payload + its share of the
    fp32 per-block scale psum), so the cut vs the 2-byte bf16 default
    is exactly ``2 / (1 + 4/1024) = 512/257``, and vs a 4-byte fp32
    wire exactly ``1024/257``."""
    from fractions import Fraction

    import jax.numpy as jnp

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.contrib.optimizers._quantized_sync import QBLOCK

    params = {"w": jnp.zeros((512, 256), jnp.bfloat16),
              "b": jnp.zeros((8192,), jnp.bfloat16)}

    def wire(**kw):
        opt = DistributedFusedAdam(lr=1e-3, **kw)
        opt.init(params, world_size=4)
        return opt.wire_bytes_per_step()

    bf16 = wire()                                  # default: storage dtype
    i8 = wire(grad_sync_dtype="int8")
    f8 = wire(grad_sync_dtype=jnp.float8_e5m2)
    f32 = wire(grad_sync_dtype=jnp.float32)
    assert i8["grad_scales"] > 0 and bf16["grad_scales"] == 0
    # i8 bytes/element = 1 payload + 4/QBLOCK scales — exact, no
    # rounding: bucket totals are QBLOCK multiples by construction
    assert i8["grad_scales"] * QBLOCK == i8["grad_payload"] * 4
    per_elt_i8 = Fraction(QBLOCK + 4, QBLOCK)
    assert Fraction(bf16["grad_sync"], i8["grad_sync"]) \
        == Fraction(2, 1) / per_elt_i8             # = 512/257
    assert Fraction(f32["grad_sync"], i8["grad_sync"]) \
        == Fraction(4, 1) / per_elt_i8             # = 1024/257
    assert f8["grad_sync"] == i8["grad_sync"]      # both 1-byte wires
    # param gather is never quantized (no error-feedback channel)
    assert i8["param_sync"] == bf16["param_sync"]
    # the flat plan reports its one hop under the dp axis, and the
    # top-level fields are exactly that hop
    assert set(i8["hops"]) == {"dp"}
    assert i8["hops"]["dp"]["grad_sync"] == i8["grad_sync"]


def test_hierarchical_wire_bytes_cross_slice_cut_exact():
    """The ``hier_*_sync`` modes' per-hop accounting: the slow (outer)
    hop's bytes — payload AND scales — are exactly ``1/dp_in`` of the
    flat plan's at the same wire dtype, which is the bench's
    ``cross_slice_wire_cut`` headline; the fast (inner) hop carries the
    full bucket like the flat plan."""
    import jax.numpy as jnp

    from apex_tpu.contrib.optimizers import DistributedFusedAdam

    params = {"w": jnp.zeros((512, 256), jnp.bfloat16),
              "b": jnp.zeros((8192,), jnp.bfloat16)}

    def wire(**kw):
        sizes = kw.pop("axis_sizes", None)
        opt = DistributedFusedAdam(lr=1e-3, **kw)
        opt.init(params, world_size=4, axis_sizes=sizes)
        return opt.wire_bytes_per_step()

    flat = wire(grad_sync_dtype="int8")
    hier = wire(grad_sync_dtype="int8", dp_axes=("dp_out", "dp_in"),
                axis_sizes={"dp_out": 2, "dp_in": 2})
    inner, outer = hier["hops"]["dp_in"], hier["hops"]["dp_out"]
    # fast hop == the flat wire (full bucket, same dtype, same scales)
    assert inner["grad_sync"] == flat["grad_sync"]
    assert inner["param_sync"] == flat["param_sync"]
    # slow hop: exactly 1/dp_in of the flat plan, scales included —
    # the cross_slice_wire_cut the bench reports is exactly dp_in
    assert outer["grad_payload"] * 2 == flat["grad_payload"]
    assert outer["grad_scales"] * 2 == flat["grad_scales"]
    assert outer["grad_sync"] * 2 == flat["grad_sync"]
    assert outer["param_sync"] * 2 == flat["param_sync"]
    # top-level fields sum the hops (total wire traffic of the step)
    assert hier["grad_sync"] == inner["grad_sync"] + outer["grad_sync"]
    # both hops stay at the compressed dtype: equal bytes/element
    # implies the slow hop never widened (3/2 = full + half buckets)
    assert hier["grad_payload"] * 2 == flat["grad_payload"] * 3


# ------------------------------------------------ bench_compare CI gate
BENCH_COMPARE = os.path.join(os.path.dirname(BENCH), "benchmarks",
                             "bench_compare.py")


def _write_round(path, parsed):
    with open(path, "w") as f:
        json.dump({"n": 1, "rc": 0, "parsed": parsed}, f)


def _run_compare(*argv, cwd=None):
    return subprocess.run(
        [sys.executable, BENCH_COMPARE, *argv],
        capture_output=True, text=True, timeout=60, cwd=cwd)


_OLD_ROUND = {
    "adam": {"speedup_vs_eager": 200.0, "speedup_vs_jitted_optax": 1.2,
             "fused_ms": 2.4},
    "gpt124_s1024": {"tokens_per_sec": 90000.0,
                     "mfu_vs_measured_roofline": 0.66},
    "zero_gpt124": {"hier_int8_sync": {"cross_slice_wire_cut": 4.0,
                                       "tokens_per_sec": 40000.0}},
}


def test_bench_compare_fails_on_headline_regression():
    """>X% drop on a named headline column exits 1 and names it;
    non-headline columns (fused_ms) never participate."""
    import copy
    import tempfile

    new = copy.deepcopy(_OLD_ROUND)
    new["gpt124_s1024"]["tokens_per_sec"] = 70000.0   # -22%
    new["adam"]["fused_ms"] = 99.0                    # not a headline
    with tempfile.TemporaryDirectory() as d:
        old_p, new_p = os.path.join(d, "a.json"), os.path.join(d, "b.json")
        _write_round(old_p, _OLD_ROUND)
        _write_round(new_p, new)
        r = _run_compare(old_p, new_p, "--json")
        assert r.returncode == 1, r.stdout + r.stderr
        report = json.loads(r.stdout)
        assert [x["column"] for x in report["regressions"]] \
            == ["gpt124_s1024.tokens_per_sec"]
        assert report["regressions"][0]["change_pct"] < -20
        # within tolerance at a looser gate
        r = _run_compare(old_p, new_p, "--max-regression-pct", "30")
        assert r.returncode == 0


def test_bench_compare_tolerance_and_missing_columns():
    """Noise inside the tolerance passes; columns missing on either
    side are skipped loudly, never failed."""
    import copy
    import tempfile

    new = copy.deepcopy(_OLD_ROUND)
    new["gpt124_s1024"]["tokens_per_sec"] = 85000.0      # -5.6% noise
    del new["zero_gpt124"]                               # lost section
    new["serve_gpt124"] = {"s8": {"tokens_per_sec": 100.0}}  # new section
    with tempfile.TemporaryDirectory() as d:
        old_p, new_p = os.path.join(d, "a.json"), os.path.join(d, "b.json")
        _write_round(old_p, _OLD_ROUND)
        _write_round(new_p, new)
        r = _run_compare(old_p, new_p, "--json")
        assert r.returncode == 0, r.stdout + r.stderr
        report = json.loads(r.stdout)
        assert not report["regressions"]
        skipped = {x["column"]: x["missing_in"]
                   for x in report["skipped"]}
        assert skipped["zero_gpt124.hier_int8_sync.cross_slice_wire_cut"] \
            == "new"
        assert skipped["serve_gpt124.s8.tokens_per_sec"] == "old"
        oknames = [x["column"] for x in report["ok"]]
        assert "gpt124_s1024.tokens_per_sec" in oknames


def test_bench_compare_newest_pair_and_extra_columns():
    """No-args mode picks the two newest BENCH_r*.json by round
    number; --columns adds extra headline globs."""
    import copy
    import tempfile

    new = copy.deepcopy(_OLD_ROUND)
    new["adam"]["fused_ms"] = 5.0  # 2x slower: only --columns sees it
    with tempfile.TemporaryDirectory() as d:
        _write_round(os.path.join(d, "BENCH_r01.json"), {"adam": {}})
        _write_round(os.path.join(d, "BENCH_r02.json"), _OLD_ROUND)
        _write_round(os.path.join(d, "BENCH_r09.json"), new)
        # the repo-root discovery walks up from benchmarks/: run from a
        # fake layout instead — two files named explicitly
        r = _run_compare(os.path.join(d, "BENCH_r02.json"),
                         os.path.join(d, "BENCH_r09.json"))
        assert r.returncode == 0
        # fused_ms got 2x WORSE but is higher-is-better under the
        # default leaves — --columns opts it in, and the gate reddens
        # (direction stays higher-is-better: a perf column opted in
        # this way should be a rate, but the crafted drop proves the
        # glob matching)
        r = _run_compare(os.path.join(d, "BENCH_r02.json"),
                         os.path.join(d, "BENCH_r09.json"),
                         "--columns", "adam.fused_ms", "--json")
        assert r.returncode == 0  # 2.4 -> 5.0 is an INCREASE
        report = json.loads(r.stdout)
        assert [x["column"] for x in report["improvements"]] \
            == ["adam.fused_ms"]


def test_bench_compare_torn_input_is_a_usage_error():
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        old_p = os.path.join(d, "a.json")
        new_p = os.path.join(d, "b.json")
        _write_round(old_p, _OLD_ROUND)
        with open(new_p, "w") as f:
            f.write('{"parsed": {"adam":')
        r = _run_compare(old_p, new_p)
        assert r.returncode == 2
        assert "bench_compare" in r.stderr


def test_bench_compare_newest_pair_orders_by_round_number():
    """r10 outranks r9 even when r9's mtime is newer (post-checkout
    mtimes lie)."""
    import importlib.util
    import tempfile

    spec = importlib.util.spec_from_file_location("bench_compare",
                                                  BENCH_COMPARE)
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    with tempfile.TemporaryDirectory() as d:
        for name in ("BENCH_r02.json", "BENCH_r10.json", "BENCH_r09.json"):
            _write_round(os.path.join(d, name), {})
        now = os.path.getmtime(os.path.join(d, "BENCH_r10.json"))
        os.utime(os.path.join(d, "BENCH_r09.json"), (now + 60, now + 60))
        pair = bc.newest_pair(d)
        assert [os.path.basename(p) for p in pair] \
            == ["BENCH_r09.json", "BENCH_r10.json"]
        assert bc.newest_pair(tempfile.mkdtemp()) is None


# ------------------------------------------- flash sweep + installer
FLASH_SWEEP = os.path.join(os.path.dirname(BENCH), "benchmarks",
                           "flash_sweep.py")
INSTALL = os.path.join(os.path.dirname(BENCH), "benchmarks",
                       "install_tuned_blocks.py")


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
            assert fap.tuned_blocks(s, d, dtype, phase=phase) == tuple(val)
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
