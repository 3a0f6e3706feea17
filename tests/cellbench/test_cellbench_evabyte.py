"""A tiny cell of the EvaByte adapter
(``cellbench/adapters/serve_evabyte.py``) through the harness on the
CPU, as ``test_cellbench_kda_mla_moe.py`` drives the KDA/MLA family's:
the run is judged ``correct`` against the plain reference, the window's
requests all finish, the per-layer metrics that are counts come out (a
time never does on the CPU), both controls are rejected, the counters
and spans feed the new readers, and the committed configuration is the
catalog's row cut as it says."""

import json
from types import SimpleNamespace

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.adapters import serve_evabyte as adapter
from cellbench.cells import Bench
from cellbench.run import run_cell

CELL = "tiny.bytedoc"
REAL = "evabyte-6.5b.serve-bytedoc-over"
MODEL = {
    "model_type": "evabyte", "attention_class": "eva", "vocab_size": 320,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "num_pred_heads": 8,
    "window_size": 32, "chunk_size": 4, "num_chunks": None,
    "rms_norm_eps": 1e-5, "rope_theta": 100000, "rope_scaling": None,
    "norm_add_unit_offset": True, "fp32_skip_add": True,
    "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "init_std": 0.08,
    "max_position_embeddings": 4096,
    "cellbench": {
        "adapter": "serve_evabyte",
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "max_context": 160, "max_prompt_len": 128,
                 "prefill_buckets": [32, 64], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret",
                 "sample_dot_dtype": "float32"},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-4, "mean_logit_gap": 1e-5,
                    "eva_summary_drift": 1e-5}},
}
MIX = {"generator": "open_loop_long",
       "arrivals": {"gaps": {"dist": "exponential"}, "rate": 4.0},
       "lengths": {"prompt": {"dist": "lognormal", "median": 40,
                              "sigma": 0.6, "min": 9, "max": 120},
                   "output": {"dist": "lognormal", "median": 16,
                              "sigma": 0.5, "min": 6, "max": 36}},
       "in_flight_at_open": 3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-eva.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-bytedoc.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-eva", "source": "test",
        "file": "cellbench/configs/tiny-eva.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-eva", "traffic": "tiny-bytedoc",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".bytegen"):
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.mark.parametrize("trace", [False, True])
def test_a_tiny_cell_runs_and_agrees_with_the_reference(root, trace):
    out = run_cell(root, CELL, 2 ** 31 + 77, 2.0, trace, require_tpu=False,
                   return_checks=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8 and out["device"]["platform"] == "cpu"
    (name, value, limit), (mean_name, mean, mean_limit), \
        (drift_name, drift, drift_limit) = out["checks"]
    assert "widest logit gap" in name and value <= limit
    assert "mean logit gap" in mean_name and mean <= mean_limit
    # the checked request's served bytes cross a window's edge
    assert "13 bytes after a prompt of 23" in name
    assert "pooled keys and values" in drift_name
    assert "6 chunks" in drift_name and "the last 7 positions by decode" \
        in drift_name
    assert 0 <= drift <= drift_limit
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = out["metrics"]
    assert set(got) == {"slot_occupancy.bytegen", "kv_pool_used.bytegen",
                        "step_hbm.bytegen"}
    assert 0 < got["slot_occupancy.bytegen"]["value"] <= 100
    assert 0 < got["kv_pool_used.bytegen"]["value"] <= 100


@pytest.mark.parametrize("control,fails", [
    ("float8_e4m3fn", (True, True, True)),
    (adapter.ALPHA_CONTROL, (False, False, True))])
def test_both_controls_are_rejected(root, control, fails):
    """The float8 reference in the program's place fails every number;
    the reference with a bfloat16 ``alpha`` moves no chosen byte and is
    rejected by the third number alone, which reads the pooling."""
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control=control, return_checks=True)
    assert tuple(v > limit for _, v, limit in out["checks"]) == fails
    assert out["correct"] is False


def test_where_the_probe_stops():
    """Half a chunk into a chunk, never past the prompt, and so that
    its decode steps stay inside a window with a quarter of it behind
    them."""
    c = SimpleNamespace(window_size=2048, chunk_size=16)
    steps = adapter.probe_steps(c)
    assert steps == adapter.PROBE_STEPS == 72
    for plen in (1024, 1500, 2047, 2048, 2049, 2500, 4100, 5000, 8192,
                 16384, 9999):
        cut = adapter.probe_cut(plen, c)
        assert cut <= plen and cut % 16 == 8
        assert (cut + steps) // 2048 == cut // 2048     # no rollover
        assert (cut + steps) % 2048 >= 512
    assert adapter.probe_cut(20, c) == 20
    tiny = SimpleNamespace(window_size=32, chunk_size=4)
    assert adapter.probe_steps(tiny) == 8
    assert adapter.probe_cut(23, tiny) == 18


def test_the_checked_request_crosses_a_window_where_one_does():
    done = lambda plen, n: SimpleNamespace(prompt=[0] * plen,
                                           tokens=[0] * n)
    pool = {0: done(3000, 500), 1: done(4000, 300), 2: done(9000, 2000),
            3: done(1900, 149), 4: done(1900, 148)}
    for seed in range(8):       # 2 is too long; 0 and 4 cross nothing
        assert adapter.pick_checked(pool, seed, 2048) in (1, 3)
    assert adapter.pick_checked({0: pool[0]}, 0, 2048) == 0
    assert adapter.pick_checked({2: pool[2]}, 0, 2048) is None


def test_the_requests_in_flight_at_the_open_are_one_set_for_every_seed():
    """The same prompt lengths and remaining answers whatever the seed
    (the generator's draw :data:`adapter.HELD_DRAW`); the seed draws
    the ids."""
    from cellbench import loadgen

    mix = Bench(REPO).cell(REAL)["traffic_file"]
    gen = loadgen.generator(mix)
    a, b = (adapter.in_flight(gen, mix, 320, seed) for seed in (1, 2 ** 31))
    shape = lambda rs: [(r.rid, len(r.prompt), r.max_new_tokens) for r in rs]
    assert len(a) == 20 and shape(a) == shape(b) == shape(
        gen.in_flight_at_open(mix, 320, adapter.HELD_DRAW))
    assert sum(n for _, _, n in shape(a)) == 12943
    assert a[0].prompt != b[0].prompt and max(map(max, (
        r.prompt for r in a))) < 320
    assert [r.prompt for r in adapter.in_flight(gen, mix, 320, 1)] \
        == [r.prompt for r in a]


def test_the_counts_behind_the_two_rooflines():
    bench = Bench(REPO)
    conf = bench.cell(REAL)["config_file"]
    ctx = {"model": conf, "args": conf["cellbench"]["args"],
           "counters": {"decode_steps": 4000, "traced_steps": 400,
                        "eva_window_cols": 80_000_000,
                        "eva_summary_cols": 40_000_000, "layers": 8,
                        # the lengths put a tenth of the window's columns
                        # into the traced stretch
                        "expected_window_cols": 79_000_000,
                        "expected_summary_cols": 41_000_000,
                        "traced_window_cols": 8_200_000,
                        "traced_summary_cols": 3_800_000},
           "spans": [{"name": "serve.prefill",
                      "attrs": {"padded_tokens": 8192}},
                     {"name": "serve.prefill",
                      "attrs": {"padded_tokens": 2048}},
                     {"name": "serve.decode_step", "attrs": {}}]}
    dec = bench.counts("eva_decode_attention").total(ctx)
    cols = 120_000_000 * 8 / 10
    assert dec == {"flops": 4.0 * 4096 * cols, "bytes": 16384.0 * cols}
    pre = bench.counts("eva_prefill_attention")
    assert pre.padded_tokens(ctx) == 10240
    work = pre.total(ctx)
    # five causal windows, and windows 1-3 of the longer prompt see 128,
    # 256 and 384 pooled pairs
    assert work["flops"] == 8 * (2.0 * 2048 * 2048 * 4096 * 5
                                 + 4.0 * 2048 * 128 * 4096 * 6)
    assert work["bytes"] == 8 * 2 * (4.0 * 2048 * 4096 * 5
                                     + 2.0 * 128 * 4096 * 6)
    none = dict(ctx, counters={"layers": 8}, spans=[])
    assert bench.counts("eva_decode_attention").total(none) is None
    assert pre.total(none) is None and pre.padded_tokens(none) == 0
    # a reader that finds nothing to read returns None and does not raise
    for name in ("eva_decode_attn_roofline.bytegen",
                 "eva_prefill_attn_roofline.bytegen",
                 "eva_prefill_attn.bytegen", "prefill_share.bytegen"):
        reader = bench.custom_reader(name)
        assert reader.read(dict(none, reduced=None, counts=bench.counts,
                                notes=[])) is None


def test_the_committed_cell_is_the_catalogs_row_cut_as_it_says():
    bench = Bench(REPO)
    cell = bench.cell(REAL)
    conf, mix = cell["config_file"], cell["traffic_file"]
    entry = next(c for c in bench.spec["configs"]
                 if c["name"] == cell["config"])
    assert cell["chips"] == 1 and entry["reduced"] == conf["reduced"] \
        == ["num_hidden_layers"]
    assert entry["source"] == conf["source"]
    # the catalog's row (``config``), copied: the catalog is no part of
    # the checkout
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    for key, value in published.items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value and conf[key] == 8
        else:
            assert conf[key] == value, key
    assert set(conf["changed"]) == set(conf["reduced"])
    assert conf["deployment"]["chips"] == conf["deployment"]["stages"] == 4
    args = conf["cellbench"]["args"]
    assert (args["max_batch"], args["max_context"], args["max_prompt_len"],
            args["temperature"]) == (20, 18432, 16384, 0.0)
    assert all(b % conf["window_size"] == 0
               for b in args["prefill_buckets"] + [args["max_prompt_len"]])
    assert set(conf["cellbench"]["correct"]) == {
        "logit_gap", "mean_logit_gap", "eva_summary_drift"}
    # the traffic the issue names
    assert mix["generator"] == "open_loop_long"
    assert mix["in_flight_at_open"] == 20
    assert mix["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.6, "min": 1024,
        "max": 16384}
    assert mix["lengths"]["output"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert mix["arrivals"]["gaps"] == {"dist": "exponential"}
    assert mix["arrivals"]["rate"] == pytest.approx(
        1.25 * mix["knee"]["requests_per_s"], rel=0.02)
    assert mix["lengths"]["prompt"]["max"] + mix["lengths"]["output"]["max"] \
        <= args["max_context"]
    # every .bytegen metric is the new cell's alone
    names = [m["name"] for m in bench.per_layer(REAL)]
    assert len(names) == 13 and all(n.endswith(".bytegen") for n in names)
    assert [m["name"] for m in bench.end_to_end(REAL)] == [
        "serve_tokens_per_s", "setup_s"]
