"""T5 pretraining example CLI: the enc-dec counterpart of the GPT
trainer — dual-stream pipeline, fp16 scaling, fused CE, all through
the command line on the virtual mesh."""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parents[1]


def _run(args):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": str(REPO),
    }
    r = subprocess.run(
        [sys.executable, str(REPO / "examples/t5/pretrain_t5.py"), *args],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-2000:]}"
    return r.stdout


def _losses(out):
    return [float(m) for m in re.findall(r"loss=([0-9.]+)", out)]


def test_pp_split_trains():
    """pp=4 split=2 x tp=2: the dual-stream pipeline runs from the CLI
    and the copy-task loss falls over the batch pool."""
    out = _run(["--pp", "4", "--split", "2", "--tp", "2", "--steps", "10",
                "--lr", "3e-3"])
    losses = _losses(out)
    assert len(losses) == 10 and losses[-1] < losses[0]


def test_fp16_fused_ce_composes():
    """--fp16 (scaler through the dual-stream schedule) x --fused-ce."""
    out = _run(["--pp", "2", "--steps", "8", "--fp16", "--fused-ce",
                "--lr", "3e-3"])
    losses = _losses(out)
    assert len(losses) == 8 and losses[-1] < losses[0]
