"""Test configuration: run everything on a virtual 8-device CPU platform.

Mirrors the reference's test strategy (SURVEY.md §4): distributed
correctness is established by comparing a parallel run against a
single-device oracle.  Multi-chip hardware isn't needed —
``xla_force_host_platform_device_count=8`` gives 8 CPU devices for
``jax.sharding.Mesh`` tests.

Tiers (the reference's L0/L1 split):

- quick: ``pytest -m "not slow" tests/`` — unit + small parity tests,
  ~2:30 on this (1-core) box.  Run on every change.
- full:  ``pytest tests/`` — adds the compiled e2e/model-level parity
  workloads (GPT 3D/MoE/ResNet trainers, ZeRO resharding + tp
  composition, HLO memory regressions, 2-process jax.distributed
  tests) and every per-test ``slow`` mark; 456 tests, ~20 min on this
  box.  CI / pre-commit.

Anything >~15 s compiled carries ``@pytest.mark.slow`` (file-level
``pytestmark`` for whole-file e2e suites).
"""

import os

# Must be set before the first JAX backend call.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

# Persistent compilation cache: the suite's wall time is dominated by
# XLA:CPU compiles, and the same programs recompile on every run.
# First run pays; re-runs hit the cache (the one every entry point
# shares: JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).
from apex_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test; `-m 'not slow'` gives the quick tier"
    )


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("need 8 virtual devices")
    return devs[:8]
