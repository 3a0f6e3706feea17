"""A tiny cell of the LFM2-MoE adapter
(``cellbench/adapters/serve_lfm2_moe.py``) through the harness on the
CPU, as ``test_cellbench_falcon_h1.py`` drives that family's: the run is
judged ``correct`` against the plain reference, the window's requests
all finish, the per-layer metrics that are counts come out (a time never
does on the CPU), every new reader reads a number of the traced run's
spans and counters and of a device trace that holds the family's
kernels, the float8 control is rejected, the new counts count what they
say and say nothing to a cell without the mechanism, and the committed
configuration is the catalog's row cut as it says."""

import json
import math
import signal

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.agentgen"
COMMITTED = "lfm2-8b-a1b.serve-agentgen-over"
CONFIG = "lfm2-8b-a1b-serve-pp2"
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv"]
MODEL = {
    "model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 9, "num_dense_layers": 1,
    "layer_types": LAYER_TYPES, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 1000000,
    "max_position_embeddings": 4096,
    "cellbench": {
        "adapter": "serve_lfm2_moe",
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "num_pages": 40, "max_context": 128, "max_prompt_len": 96,
                 "prefill_buckets": [16, 32], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret",
                 "sample_dot_dtype": "float32"},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-3, "mean_logit_gap": 1e-4,
                    "conv_tail_drift": 1e-4, "widest_tail_drift": 1e-4}},
}
MIX = {"generator": "open_loop_long",
       "arrivals": {"gaps": {"dist": "exponential"}, "rate": 4.0},
       "lengths": {"prompt": {"dist": "lognormal", "median": 20,
                              "sigma": 0.8, "min": 3, "max": 90},
                   "output": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 16}},
       "in_flight_at_open": 3}
#: the metrics this PR adds: what no accepted metric's reader computes.
#: BENCHMARK.json may hold 128 per-layer metrics and held 123, so the
#: cell's other readings are ACCEPTED metrics whose readers are generic,
#: with the cell appended to their ``workloads`` (PERF.md, section 7)
NEW_METRICS = ("mfu.agentgen", "decode_attn.agentgen",
               "decode_attn_roofline.agentgen", "short_conv.agentgen")
SHARED_METRICS = (
    "host_iter.serve", "decode_step.longgen", "moe_experts.longgen",
    "moe_experts_roofline.longgen", "moe_tokens_per_expert.longgen",
    "prefill_share.longgen", "slot_occupancy.longgen", "kv_pool_used.longgen",
    "step_hbm.longgen", "sched_host.longgen", "idle_in_call.longgen",
    "device_idle.longgen")
CELL_METRICS = NEW_METRICS + SHARED_METRICS
#: seconds a test of this file may take (the slowest takes 40 here)
TIME_LIMIT = 300


@pytest.fixture(autouse=True)
def _time_limit():
    def stop(*_):
        raise TimeoutError(f"over this file's limit of {TIME_LIMIT} s")

    old = signal.signal(signal.SIGALRM, stop)
    signal.alarm(TIME_LIMIT)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-lfm2.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-agentgen.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-lfm2", "source": "test",
        "file": "cellbench/configs/tiny-lfm2.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-lfm2", "traffic": "tiny-agentgen",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"] in CELL_METRICS:
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="module")
def traced(root):
    """ONE traced tiny run: its result, and the program's tracer still
    holds its spans."""
    return run_cell(root, CELL, 2 ** 31 + 77, 2.0, True, require_tpu=False,
                    return_checks=True)


def _checks(out):
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8 and out["device"]["platform"] == "cpu"
    (name, value, limit), (mean_name, mean, mean_limit), \
        (tail_name, drift, drift_limit), (all_name, widest, widest_limit) \
        = out["checks"]
    assert "widest logit gap" in name and value <= limit
    assert "mean logit gap" in mean_name and mean <= mean_limit
    # the float32 program's tail of the deepest convolution layer before
    # any router (layer 2) is the float32 reference's, after the probe's
    # decode steps (64, or as many as the slot's 128 positions leave)
    assert "convolution layer's tail (layer 2, " in tail_name
    assert "by decode steps" in tail_name
    assert 0 < drift <= 1e-5 < drift_limit
    # ... and so is every other convolution layer's
    assert "widest distance of the 7 convolution layers' tails" in all_name
    assert "over 8 readings two decode steps apart" in all_name
    assert drift <= widest <= 1e-5 < widest_limit


def test_a_tiny_cell_runs_and_agrees_with_the_reference(root):
    out = run_cell(root, CELL, 2 ** 31 + 76, 2.0, False, require_tpu=False,
                   return_checks=True)
    _checks(out)
    assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number


def test_a_traced_tiny_run_reports_the_counts(traced):
    _checks(traced)
    got = traced["metrics"]
    assert set(got) == {"slot_occupancy.longgen", "kv_pool_used.longgen",
                        "step_hbm.longgen", "moe_tokens_per_expert.longgen"}
    assert 0 < got["slot_occupancy.longgen"]["value"] <= 100
    assert 0 < got["kv_pool_used.longgen"]["value"] <= 100
    # at most 4 slots x 2 a token over 8 experts: a row an expert a step
    assert 0 < got["moe_tokens_per_expert.longgen"]["value"] <= 1.0


def test_the_control_precision_is_rejected(root):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control="float8_e4m3fn", return_checks=True)
    (_, value, limit), (_, mean, mean_limit), (_, drift, drift_limit), \
        (_, widest, widest_limit) = out["checks"]
    assert value > limit and mean > mean_limit and drift > drift_limit
    assert widest >= drift and out["correct"] is False


@pytest.mark.parametrize("fault", ["stale_conv_tail", "wrong_conv_tail"])
def test_a_fault_planted_in_the_scans_last_repeat_is_rejected(root, fault):
    """The PROGRAM runs with the convolution layers of the scan's LAST
    repeat (layers 6, 8 and 9 of the tiny model: a leading dense layer,
    then ``conv, attention, conv, conv`` twice under one scan) leaving
    their tails as the prefill wrote them, or reading and shifting those
    of the repeat before (layers 2, 4 and 5).  Left stale, the tail
    before any router (layer 2's) is as sound as ever and only the
    widest shows a tail that is not the sequence's own."""
    from apex_tpu.ops import kda

    sound = kda.conv_step
    out = run_cell(root, CELL, 2 ** 31 + 79, 1.0, False, require_tpu=False,
                   control=fault, return_checks=True)
    assert kda.conv_step is sound
    _, (_, mean, mean_limit), (_, drift, drift_limit), \
        (name, widest, widest_limit) = out["checks"]
    assert mean > mean_limit and (drift <= 1e-5 < drift_limit
                                  or fault == "wrong_conv_tail")
    assert "widest distance of the 7" in name and widest > 0.5 > widest_limit
    assert out["correct"] is False and out["failed"] == 0


def _readers(ctx_counters, spans, config=CONFIG):
    from cellbench.cells import Bench

    bench = Bench(REPO)
    conf = json.loads((REPO / "cellbench" / "configs"
                       / f"{config}.json").read_text())
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "agentgen-1.25knee.json").read_text())
    return bench, {"model": conf, "args": conf["cellbench"]["args"],
                   "counters": ctx_counters, "spans": spans,
                   "traffic": mix, "chips": 1, "reduced": None,
                   "peaks": bench.peaks("TPU v5 lite"),
                   "counts": bench.counts, "notes": []}


#: what the adapter hands the readers of a window of 1,000 steps at the
#: committed sizes, every slot live
COUNTERS = {
    "decode_steps": 1000, "traced_steps": 100, "conv_layers": 10,
    "attn_layers": 3, "moe_layers": 12, "experts_held": 32,
    "conv_state_updates": 1000 * 10 * 256,
    "moe_assignments_held": 1000 * 12 * 1024,
    "moe_assignments_all": 1000 * 12 * 1024,
    "moe_experts_hit": 1000 * 12 * 32, "traced_kv_positions": 100 * 256 * 900,
    "traced_decode_tokens": 100 * 256, "window_s": 25.0,
    "window_tokens": 256000, "window_prompt_tokens": 80000,
    "slot_occupancy_pct": 99.0, "kv_pool_used_pct": 85.0,
    "step_hbm_GB": 12.5}


def test_the_new_counts_against_hand_arithmetic():
    """``counts/lfm2_moe_model.py``: the stage's matrices a token;
    ``counts/hybrid_decode_attention.py``: 6,144 B a position over the
    THREE attention layers; all say nothing where the counters or the
    mechanism are not there."""
    bench, ctx = _readers(dict(COUNTERS), [])
    attn = bench.counts("hybrid_decode_attention")
    assert attn.attention_layers(ctx["model"]) == 3
    got = attn.total(ctx)
    assert got["bytes"] == 6144 * 100 * 256 * 900
    assert got["flops"] == 4 * 32 * 64 * 3 * 100 * 256 * 900
    model = bench.counts("lfm2_moe_model")
    assert model.layer_kinds(ctx["model"]) == (10, 3, 1, 12)
    weights = model.token_matrix_weights(ctx["model"])
    assert weights == 10 * 16777216 + 3 * 10485760 + 44040192 \
        + 12 * (65536 + 4 * 11010048)
    # one decode token over no context: 2 a weight, the taps, the head
    one = model.flops(ctx["model"], 1, 1, 0)
    assert one == 2 * weights + 10 * 2 * 3 * 2048 + 2 * 65536 * 2048
    mfu = bench.custom_reader("mfu.agentgen").read(ctx)
    want = model.flops(ctx["model"], 336000, 256000,
                       256000 * 900 + 80000 * 128) / 25.0 / 197e12 * 100
    assert math.isclose(mfu, want) and 1 < mfu < 30
    experts = bench.counts("moe_experts").total(ctx)     # the file's keys
    assert experts["bytes"] == 3 * 2048 * 1792 * 2 * 100 * 12 * 32
    assert bench.custom_reader("moe_tokens_per_expert.longgen") \
        .read(ctx) == 32.0
    _, bare = _readers({"decode_steps": 1000, "traced_steps": 100}, [])
    assert attn.total(bare) is None
    for name in ("decode_attn_roofline.agentgen", "mfu.agentgen",
                 "moe_experts_roofline.longgen",
                 "moe_tokens_per_expert.longgen", "prefill_share.longgen",
                 "idle_in_call.longgen"):
        assert bench.custom_reader(name).read(bare) is None


def test_every_new_reader_reads_a_number(traced):
    """All sixteen metrics of the cell (four new, twelve accepted ones
    it joins), read as the harness reads them (``readers.read``), from
    the traced tiny run's spans (the program's
    tracer still holds them), the counters above and a device trace that
    holds a step's kernels under their names: each gives a number, no
    share of a roofline or a peak over 100."""
    from cellbench import readers, span_readers
    from cellbench.trace.reduce import Reduced

    spans = span_readers.program_spans()
    steps = [s for s in spans if s["name"] == "serve.decode_step"]
    assert len(steps) > 20 and any(s["name"] == "serve.prefill"
                                   for s in spans)
    bench, ctx = _readers(dict(COUNTERS), spans)
    ms, ops, programs, host = 10 ** 6, [], [], []
    for i in range(100):                # 100 steps of 25 ms, 1 ms idle each
        t = i * 26 * ms
        programs.append(["jit_step(123)", t, 25 * ms])
        host.append(["serve.decode_step", t - ms // 2, 26 * ms])
        ops += [["%gmm.7 = bf16[1024,1792] custom-call(...)", t, 14 * ms],
                ["%apex_decode_attention.2 = custom-call(...)", t + 14 * ms,
                 6 * ms],
                ["%apex_kda_conv_step.5 = custom-call(...)", t + 20 * ms,
                 1 * ms],
                ["%fusion.9 = bf16[256,2048] fusion(...)", t + 21 * ms,
                 4 * ms]]
    t = 100 * 26 * ms
    programs.append(["jit_prefill(7)", t, 30 * ms])
    ops.append(["%gmm.8 = bf16[2048,1792] custom-call(...)", t, 30 * ms])
    red = Reduced({"tpu0": ops}, 0, t + 40 * ms, host_spans=host,
                  modules={"tpu0": programs})
    ctx["reduced"] = red
    got = {}
    per_layer = bench.per_layer(COMMITTED)
    assert sorted(m["name"] for m in per_layer) == sorted(CELL_METRICS)
    for m in per_layer:
        name = m["name"].rsplit(".", 1)[0]
        got[name] = readers.read(dict(m), ctx, bench.custom_reader(m["name"]))
        if name == "host_iter" and got[name] is None:
            # an accepted reader of the program's own tracer: it leaves
            # out every period near a stall of a second, and under six
            # test workers a tiny run may be nothing else (PERF.md,
            # section 7: the loop readers' unsteady cases)
            continue
        assert isinstance(got[name], float) and got[name] > 0, name
        if "roofline" in name or name == "mfu":
            assert got[name] <= 100.0, (name, got[name])
    assert math.isclose(got["moe_experts"], 14.0)       # the prefill's not
    assert math.isclose(got["decode_attn"], 6.0)
    assert math.isclose(got["short_conv"], 1.0)
    # 8.46 GB of experts in 14 ms: 73.7% of 819 GB/s
    assert 70 < got["moe_experts_roofline"] < 77
    assert 0.9 < got["idle_in_call"] <= 1.0
    assert 1.0 < got["prefill_share"] < 1.2
    assert len(got) == 16


def test_every_new_metric_reads_nothing_in_a_cell_without_the_mechanism():
    """Handed the GPT-2 serving configuration and the counters its
    adapter gives (what a PARENT's line would hold), no NEW reader
    raises; without a device trace each returns None or a plain
    counter's value, and with one that lacks the family's kernels the
    family's own metrics still say nothing."""
    from cellbench import readers
    from cellbench.trace.reduce import Reduced

    bench, ctx = _readers(
        {"decode_steps": 100, "traced_steps": 10, "traced_kv_positions": 9,
         "traced_decode_tokens": 3, "slot_occupancy_pct": 50.0},
        [{"name": "serve.prefill", "ts": 0.0, "dur_us": 10.0,
          "attrs": {"padded_tokens": 64}}],
        config="gpt2-large-serve")
    ms = 10 ** 6
    red = Reduced({"tpu0": [["%fusion.1 = f32[8] fusion(...)", 0, ms]]},
                  0, 2 * ms)
    for reduced in (None, red):
        for name in NEW_METRICS:
            m = next(m for m in bench.per_layer(COMMITTED)
                     if m["name"] == name)
            value = readers.read(dict(m), dict(ctx, reduced=reduced),
                                 bench.custom_reader(m["name"]))
            # no trace, or a trace without the family's kernels
            assert value is None, name


def test_the_committed_configuration_is_the_catalog_row_cut_as_listed():
    import sys

    sys.path.insert(0, str(REPO / "tests"))
    from test_serve_lfm2_moe import CATALOG

    conf = json.loads((REPO / "cellbench" / "configs"
                       / f"{CONFIG}.json").read_text())
    want = {"num_hidden_layers": (24, 13), "num_dense_layers": (2, 1),
            "layer_types": (CATALOG["layer_types"],
                            CATALOG["layer_types"][:13])}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted(want)
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
        assert CATALOG[key] == published
    # none of them a width; every other key of the row as it stands:
    # every width, all 32 experts, all 65,536 rows
    for key, value in CATALOG.items():
        if key not in want:
            assert conf[key] == value, key
    assert (conf["num_experts"], conf["vocab_size"]) == (32, 65536)
    # the floors: whole periods, 12 >= 4 layers after the dense one
    kinds = conf["layer_types"]
    assert kinds.count("full_attention") == 3 and kinds.count("conv") == 10
    assert kinds[1:5] == kinds[5:9] == kinds[9:13] \
        == ["conv", "full_attention", "conv", "conv"]
    assert conf["num_hidden_layers"] - conf["num_dense_layers"] == 12
    assert conf["deployment"]["stages"] == 2
    assert conf["deployment"]["chips_sharing_a_layer"] == 1
    assert conf["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                              "blob/main/config.json")
    for key in ("tie_word_embeddings", "conv_order", "conv_activation",
                "q_k_norm", "rope", "router", "hidden_act", "leaf_names",
                "weights", "expert_bias", "final_gain", "dtypes", "slots",
                "prefill", "correct"):
        assert conf["assumed"][key]
    # the issue's arithmetic, from param_shapes
    import jax
    from apex_tpu.models.lfm2_moe import param_shapes
    from cellbench.adapters.serve_lfm2_moe import decode_config, model_config

    cfg, dcfg = model_config(conf), decode_config(conf, 0)
    count = lambda tree: sum(math.prod(s) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = param_shapes(cfg)
    assert count(shapes["prefix"]) == 60827648          # the dense layer
    conv_moe, attn_moe = 369174560, 362877088
    assert [count(p) for p in shapes["period"]] \
        == [3 * conv_moe, 3 * attn_moe, 3 * conv_moe, 3 * conv_moe]
    assert shapes["suffix"] == [] and "head" not in shapes
    assert count(shapes) == 4606249728                  # 9.21 GB in bf16
    assert 9.21e9 < 2 * count(shapes) < 9.22e9
    spec = cfg.served_model().cache_spec()
    assert spec["k"] == spec["v"] == (3, 8, 64)         # 6,144 B a position
    assert (spec["conv_tail"].layers, spec["conv_tail"].shape) \
        == (10, (2 * 2048,))
    assert (dcfg.max_batch, dcfg.cache.num_pages, dcfg.cache.page_size,
            dcfg.cache.pages_per_seq) == (256, 3840, 128, 24)
    assert dcfg.prefill_lengths == (128, 256, 512, 1024)
    pool = 2 * 3 * 8 * 64 * 2 * 128 * dcfg.cache.num_pages
    assert 3.01e9 < pool < 3.03e9
    # the cell, its traffic and its metrics
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == COMMITTED]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "agentgen-1.25knee", 1)
    assert len(spec["workloads"]) >= 10
    assert all(w["chips"] == 1 for w in spec["workloads"])
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    e2e = next(m for m in spec["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert COMMITTED in e2e["workloads"]        # wherever later cells go
    mine = [m["name"] for m in spec["per_layer"]
            if COMMITTED in m.get("workloads", ())]
    assert sorted(mine) == sorted(CELL_METRICS)
    assert sorted(m["name"] for m in spec["per_layer"]
                  if m.get("workloads") == [COMMITTED]) == sorted(NEW_METRICS)
    assert all(m["moves"] == "serve_tokens_per_s" for m in spec["per_layer"]
               if m["name"] in CELL_METRICS)
    assert len(spec["per_layer"]) <= 128        # the contract's limit


def test_the_traffic_file_is_the_mix_named_and_fits_the_configuration():
    from cellbench import loadgen

    conf = json.loads((REPO / "cellbench" / "configs"
                       / f"{CONFIG}.json").read_text())
    args = conf["cellbench"]["args"]
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "agentgen-1.25knee.json").read_text())
    assert mix["generator"] == "open_loop_long"
    assert mix["arrivals"]["gaps"] == {"dist": "exponential"}
    assert mix["in_flight_at_open"] == 256 == args["max_batch"]
    assert mix["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
        "max": 1024}
    assert mix["lengths"]["output"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert math.isclose(mix["arrivals"]["rate"],
                        1.25 * mix["knee"]["requests_per_s"])
    assert (args["temperature"], args["top_k"]) == (0.0, 0)     # greedy
    gen = loadgen.generator(mix)
    every = gen.requests(mix, conf["vocab_size"], 2 ** 31 + 5, 51.0) \
        + gen.in_flight_at_open(mix, conf["vocab_size"], 2 ** 31 + 5)
    assert len(every) > 256 + 300
    for r in every:
        assert 1 <= len(r.prompt) <= args["max_prompt_len"]
        assert len(r.prompt) + r.max_new_tokens <= args["max_context"]
        assert max(r.prompt) < conf["vocab_size"]
    # ids over all 65,536 rows, no prefix shared
    assert max(max(r.prompt) for r in every) > 65000
    # what admission reserves for 256 requests is inside the pool
    pages = sorted(-(-(len(r.prompt) + r.max_new_tokens)
                     // args["page_size"]) for r in every)
    mean = sum(pages) / len(pages)
    assert 10.5 < mean < 13 and 256 * mean < 0.85 * (args["num_pages"] - 1)
