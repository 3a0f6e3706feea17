"""The harness end to end at a tiny size on the CPU, kernels through the
Pallas interpreter.  A CPU run reports counts and ``device.platform:
cpu`` and never a device metric.  These tests skip the harness's look
for a chip (``require_tpu=False``) and drive the rest of a run."""

import json
import shutil
import subprocess
import sys

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

SEED = 2 ** 31 + 77
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,trace", [("tiny.train", False),
                                        ("tiny.train", True),
                                        ("tiny.chat", False),
                                        ("tiny.chat", True),
                                        # ZeRO over 4 of the suite's
                                        # virtual devices: the same adapter
                                        ("tiny.zero", False)])
def test_a_cpu_run_is_correct_and_reports_no_device_metric(root, cell, trace):
    out = run_cell(root, cell, SEED, 2.0, trace, require_tpu=False)
    assert set(out) == RESULT_KEYS            # no breakdown off the chip
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 4 or cell != "tiny.zero"
    assert "busy_s" not in out["device"]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    counters = {m["name"] for m in spec["per_layer"]
                if m["source"] == "program_counter"}
    allowed = counters if trace else {"setup_s"}
    assert set(out["metrics"]) <= allowed and out["metrics"]
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    json.dumps(out)


def test_a_config_a_mix_and_a_metric_are_files_found_by_name(root):
    """Adding a cell is adding files and entries: a throw-away
    configuration, mix, per-layer metric (with a reader module of its
    own) and kernel count, and not one edit of the harness."""
    data = root / "cellbench"
    conf = json.loads((data / "configs" / "tiny-serve.json").read_text())
    conf["cellbench"]["args"]["max_batch"] = 2
    (data / "configs" / "added-serve.json").write_text(json.dumps(conf))
    mix = json.loads((data / "traffic" / "tiny-chat.json").read_text())
    mix["arrivals"]["rate"] = 3.0
    mix["lengths"]["output"].update(median=5, max=8)
    (data / "traffic" / "added-mix.json").write_text(json.dumps(mix))
    (data / "counts" / "added_count.py").write_text(
        "def per_request(ctx):\n    return ctx['args']['max_batch'] * 10\n")
    (data / "layer_metrics" / "added.metric.json").write_text(json.dumps(
        {"name": "added.metric", "layer": "scheduler", "what": "test"}))
    (data / "layer_metrics" / "added.metric.py").write_text(
        "def read(ctx):\n"
        "    return ctx['counts']('added_count').per_request(ctx) + 0.5\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(
        {"name": "added-serve", "source": "test", "reduced": [],
         "file": "cellbench/configs/added-serve.json", "why": "test"})
    spec["workloads"].append(
        {"name": "added.cell", "config": "added-serve",
         "traffic": "added-mix", "chips": 1, "why": "test"})
    spec["per_layer"].append(
        {"name": "added.metric", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "gap_p95_ms", "workloads": ["added.cell"]})
    for m in spec["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "gap_p95_ms"):
            m["workloads"].append("added.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_cell(root, "added.cell", 5, 2.0, True, require_tpu=False)
    assert out["correct"] is True
    assert out["attempted"] == 6              # 3 a second for 2 seconds
    assert out["metrics"] == {"added.metric": {"value": 20.5,
                                               "unit": "count"}}


def test_an_adapter_is_a_file_found_by_the_name_a_config_gives(root):
    """``cellbench/adapters/<name>.py`` by the configuration's
    ``adapter`` key: a name with no file says which file is missing."""
    data = root / "cellbench"
    conf = json.loads((data / "configs" / "tiny-serve.json").read_text())
    conf["cellbench"]["adapter"] = "serve_moe"
    (data / "configs" / "tiny-serve.json").write_text(json.dumps(conf))
    try:
        with pytest.raises(SystemExit, match="adapters/serve_moe.py"):
            run_cell(root, "tiny.chat", 5, 1.0, False, require_tpu=False)
    finally:
        conf["cellbench"]["adapter"] = "serve"
        (data / "configs" / "tiny-serve.json").write_text(json.dumps(conf))


def test_the_training_control_comes_out_not_correct(root):
    """The reference at the next lower precision, put in the program's
    place, has to fail the comparison the program passes."""
    out = run_cell(root, "tiny.train", SEED, 2.0, False, require_tpu=False,
                   control="float8_e4m3fn")
    assert out["correct"] is False


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 77])
def test_the_serving_control_comes_out_not_correct(root, seed):
    """The serving comparison with the lower precision in the program's
    place: at each position of the same prompts and tokens it reads the
    gap of the token that float8 puts first.  At this size greedy
    streams repeat themselves and hold few close calls, so the control
    is read over seeded random streams (it need not decode), the sound
    side over what the tiny server really served (tests above)."""
    import numpy as np

    from cellbench import weights
    from cellbench.adapters import serve

    conf = json.loads((root / "cellbench" / "configs" / "tiny-serve.json")
                      .read_text())
    rng = np.random.RandomState(seed % 2 ** 32)
    served = [(rng.randint(0, conf["vocab_size"], size=8).tolist(),
               rng.randint(0, conf["vocab_size"], size=50).tolist())
              for _ in range(5)]
    key = weights.seed_key(seed)
    limit = conf["cellbench"]["correct"]
    (_, gap, _), = serve.compare(conf, key, served, limit,
                                 quant="float8_e4m3fn")
    assert gap > 3 * limit["logit_gap"]
    # the same streams scored as served tokens are far off the
    # reference's best, as any stream but the greedy one is
    (_, off, _), = serve.compare(conf, key, served, limit)
    assert off > gap


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    import apex_tpu.models.gpt as gpt

    real = gpt.make_train_step

    def broken(*a, **kw):
        step = real(*a, **{**kw, "donate_state": False})

        class Unchanged:
            __name__ = "unchanged_step"
            lower = staticmethod(step.lower)

            def __call__(self, params, state, tokens, targets):
                return params, state, step(params, state, tokens,
                                           targets)[-1]

        return Unchanged()

    monkeypatch.setattr(gpt, "make_train_step", broken)
    out = run_cell(root, "tiny.train", SEED, 1.0, False, require_tpu=False)
    assert out["correct"] is False and out["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from apex_tpu.inference.scheduler import ContinuousBatchingScheduler

    real = ContinuousBatchingScheduler._call

    def altered(self, attr, *args):
        pools, tokens = real(self, attr, *args)
        if attr == "_decode":
            tokens = (tokens + 1) % self.config.vocab_size
        return pools, tokens

    monkeypatch.setattr(ContinuousBatchingScheduler, "_call", altered)
    out = run_cell(root, "tiny.chat", SEED, 2.0, False, require_tpu=False)
    assert out["correct"] is False


def test_a_refused_request_counts_as_failed(root, monkeypatch):
    from apex_tpu.inference.scheduler import ContinuousBatchingScheduler

    real = ContinuousBatchingScheduler.submit

    def refusing(self, request):
        if request.rid == 2:
            raise ValueError("refused by the test")
        return real(self, request)

    monkeypatch.setattr(ContinuousBatchingScheduler, "submit", refusing)
    out = run_cell(root, "tiny.chat", SEED, 1.0, False, require_tpu=False)
    assert out["failed"] == 1 and out["correct"] is False


def _run(cmd, cwd):
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300)


def test_the_command_refuses_to_run_without_a_tpu():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]["name"]
    cmd = [sys.executable, *spec["command"][1:], "--workload", cell,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = _run(cmd, REPO)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_the_command_refuses_a_directory_without_the_system(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for path in spec["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable, *spec["command"][1:], "--workload",
           spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    done = _run(cmd, tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
