"""``BENCHMARK.json`` against the contract the driver holds it to, and
against the files it names."""

import json
import re

import pytest

from cellbench_tiny import REPO

from cellbench.cells import Bench

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection).*size"
                   r"|_dim$|_rank$|head_size|expansion|experts_per_tok"
                   r"|^n_embd$|^n_inner$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells has to fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_line(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (REPO / p).is_dir()


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    names = [c["name"] for c in SPEC["configs"]]
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        body = json.loads((REPO / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in body["changed"]
        # what the file says it changed is what the entry lists
        assert sorted(body["changed"]) == sorted(c["reduced"])
        assert (REPO / "cellbench" / "adapters"
                / f"{body['cellbench']['adapter']}.py").exists()
        assert all(v > 0 for v in body["cellbench"]["correct"].values())


def test_workloads():
    assert 1 <= len(SPEC["workloads"]) <= 24
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        mix = json.loads((REPO / "cellbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        if mix["generator"] == "open_loop":
            assert mix["arrivals"]["rate"] > 0
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


def _metric_ok(m, extra):
    assert set(m) - {"workloads"} == {"name", "unit", "better",
                                      "source"} | extra
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_metrics():
    e2e, layer = SPEC["end_to_end"], SPEC["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    for m in e2e:
        _metric_ok(m, {"bound"})
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e_names = {m["name"] for m in e2e}
    for m in layer:
        _metric_ok(m, {"layer", "moves"})
        assert _line(m["layer"]) and m["moves"] in e2e_names
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        # its own file, whose layer agrees letter for letter
        own = json.loads((REPO / "cellbench" / "layer_metrics"
                          / f"{m['name']}.json").read_text())
        assert own["layer"] == m["layer"]
        reader = REPO / "cellbench" / "layer_metrics" / f"{m['name']}.py"
        assert ("reader" in own) != reader.exists()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    bench = Bench(REPO)
    e2e = [m["name"] for m in bench.end_to_end(cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = bench.per_layer(cell)
    assert layer
    for m in layer:
        # a per-layer metric lists only cells that report what it moves
        assert m["moves"] in e2e
    loaded = bench.cell(cell)
    assert loaded["config_file"]["cellbench"]["adapter"]
    assert loaded["traffic_file"]["generator"]


def test_a_device_outside_the_table_of_peaks_is_an_error():
    bench = Bench(REPO)
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="peaks.json"):
        bench.peaks("TPU v9 imaginary")


def test_an_unknown_workload_is_an_error():
    with pytest.raises(SystemExit, match="no workload"):
        Bench(REPO).cell("no.such.cell")
