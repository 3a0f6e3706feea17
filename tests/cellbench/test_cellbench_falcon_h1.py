"""A tiny cell of the Falcon-H1 adapter
(``cellbench/adapters/serve_falcon_h1.py``) through the harness on the
CPU, as ``test_cellbench_kda_mla_moe.py`` drives that family's: the run
is judged ``correct`` against the plain reference, the window's requests
all finish, the per-layer metrics that are counts come out (a time never
does on the CPU), the float8 control is rejected, the bfloat16-state
control is rejected by the state number, the new counts and readers
count what they say and say nothing to a cell without the mechanism, and
the committed configuration is the catalog's row cut as it says."""

import json
import math

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.h1chat"
COMMITTED = "falcon-h1-34b.serve-chat-over"
MODEL = {
    "model_type": "falcon_h1", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 32, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "mamba_expand": 2, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attn_layer_indices": None, "attention_bias": False, "mlp_bias": False,
    "projectors_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_scaling": None,
    "rope_theta": 100000000000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4096,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "cellbench": {
        "adapter": "serve_falcon_h1",
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "num_pages": 40, "max_context": 128, "max_prompt_len": 96,
                 "prefill_buckets": [16, 32], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret",
                 "sample_dot_dtype": "float32"},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-3, "mean_logit_gap": 1e-4,
                    "ssm_state_drift": 1e-4}},
}
MIX = {"generator": "open_loop_long",
       "arrivals": {"gaps": {"dist": "exponential"}, "rate": 4.0},
       "lengths": {"prompt": {"dist": "lognormal", "median": 20,
                              "sigma": 0.8, "min": 3, "max": 90},
                   "output": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 16}},
       "in_flight_at_open": 3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-h1.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-h1chat.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-h1", "source": "test",
        "file": "cellbench/configs/tiny-h1.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-h1", "traffic": "tiny-h1chat",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".h1chat"):
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
        (state_name, drift, drift_limit) = out["checks"]
    assert "widest logit gap" in name and value <= limit
    assert "mean logit gap" in mean_name and mean <= mean_limit
    # the probe ran to the end of the slot's pages (128 positions), the
    # float32 program's state is the float32 recurrence's
    assert "first layer's recurrent state" in state_name
    assert 0 < drift <= 1e-5 < drift_limit
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = out["metrics"]
    assert set(got) == {"slot_occupancy.h1chat", "kv_pool_used.h1chat",
                        "step_hbm.h1chat"}
    assert 0 < got["slot_occupancy.h1chat"]["value"] <= 100
    assert 0 < got["kv_pool_used.h1chat"]["value"] <= 100


def test_the_control_precision_is_rejected(root):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control="float8_e4m3fn", return_checks=True)
    (_, value, limit), (_, mean, mean_limit), (_, drift, drift_limit) \
        = out["checks"]
    assert value > limit and mean > mean_limit and drift > drift_limit
    assert out["correct"] is False


def test_a_bfloat16_state_is_rejected_and_by_the_state_number(root):
    """The second control (``control="ssm_state_bfloat16"``: the
    reference with its recurrent state rounded to bfloat16 after every
    token, in the program's place) through the harness's own judge: the
    third number, which reads the state itself, is a hundred times its
    sound reading, and the run comes out not correct."""
    out = run_cell(root, CELL, 2 ** 31 + 79, 1.0, False, require_tpu=False,
                   control="ssm_state_bfloat16", return_checks=True)
    (name, _, _), _, (state_name, drift, drift_limit) = out["checks"]
    assert "widest logit gap" in name
    assert "first layer's recurrent state" in state_name
    assert drift > 1e-3 > drift_limit and out["correct"] is False


def test_the_state_control_rounds_on_the_bits():
    """The reference's two controls are ``reference/evabyte.py``'s
    (``_rounded``: integer operations on the bits, which no compiler can
    drop as excess precision; ``test_cellbench_evabyte.py`` holds it to
    a cast's result), and a bfloat16 state moves the recurrence."""
    import jax.numpy as jnp
    import numpy as np

    from cellbench.reference import evabyte, falcon_h1 as reference

    assert reference._rounded is evabyte._rounded
    rng = np.random.RandomState(0)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    args = (f(40, 2, 4), jnp.abs(f(40, 2)) * 0.1, -jnp.abs(f(2)) - 1.0,
            f(40, 1, 8), f(40, 1, 8), jnp.ones((2,)))
    _, exact = reference.state_space(*args)
    _, low = reference.state_space(*args, state_dtype=jnp.bfloat16)
    drift = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < drift < 3e-2


def _readers(ctx_counters, spans, config="falcon-h1-34b-serve-pp9"):
    from cellbench.cells import Bench

    bench = Bench(REPO)
    conf = json.loads((REPO / "cellbench" / "configs"
                       / f"{config}.json").read_text())
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "h1chat-1.25knee.json").read_text())
    return bench, {"model": conf, "args": conf["cellbench"]["args"],
                   "counters": ctx_counters, "spans": spans,
                   "traffic": mix, "chips": 1, "reduced": None,
                   "peaks": bench.peaks("TPU v5 lite"),
                   "counts": bench.counts, "notes": []}


H1_METRICS = ("decode_step", "ssd_decode", "ssd_decode_roofline",
              "ssd_prefill", "decode_attn", "decode_attn_roofline",
              "prefill_share", "slot_occupancy", "kv_pool_used", "step_hbm",
              "idle_in_call", "device_idle", "mfu")


def test_the_new_kernels_work_is_counted_from_counters_and_shapes():
    """``counts/ssd_decode.py``: an update moves the 4 MB state twice
    (read, write), scaled to the traced steps;
    ``counts/gqa_decode_attention.py``: a position's keys and values
    once a key/value head (2 KB a layer), 4 operations a query head's
    value; ``counts/falcon_h1_model.py``: a layer is 430.1 M weights in
    its matrices; all say nothing where the counters are not there."""
    bench, ctx = _readers(
        {"decode_steps": 1000, "traced_steps": 100, "ssm_layers": 8,
         "ssm_state_updates": 1000 * 8 * 96, "traced_kv_positions": 5000,
         "traced_decode_tokens": 10, "window_s": 50.0,
         "window_tokens": 150000, "window_prompt_tokens": 100000}, [])
    state = 32 * 128 * 256
    work = bench.counts("ssd_decode").total(ctx)
    assert work["bytes"] == 2 * 4 * state * 100 * 8 * 96
    assert work["flops"] == 6 * state * 100 * 8 * 96
    attn = bench.counts("gqa_decode_attention").total(ctx)
    assert attn["bytes"] == 2 * 4 * 128 * 2 * 8 * 5000
    assert attn["flops"] == 4 * 20 * 128 * 8 * 5000
    model = bench.counts("falcon_h1_model")
    weights = model.layer_matrix_weights(ctx["model"])
    assert weights == 5120 * 3584 + 2560 * 5120 + 5120 * 9248 \
        + 4096 * 5120 + 3 * 5120 * 21504
    # one decode token over no context: 2 a weight, the state, the head
    one = model.flops(ctx["model"], 1, 1, 0)
    assert one == 8 * (2 * weights + 6 * state) + 2 * 65280 * 5120
    mfu = bench.custom_reader("mfu.h1chat").read(ctx)
    want = model.flops(ctx["model"], 250000, 150000,
                       150000 * 500 + 100000 * 96) / 50.0 / 197e12 * 100
    assert math.isclose(mfu, want) and 5 < mfu < 30
    _, bare = _readers({"decode_steps": 1000, "traced_steps": 100}, [])
    assert bench.counts("ssd_decode").total(bare) is None
    assert bench.counts("gqa_decode_attention").total(bare) is None
    for name in ("ssd_decode_roofline", "ssd_prefill",
                 "decode_attn_roofline", "prefill_share", "mfu"):
        assert bench.custom_reader(name + ".h1chat").read(bare) is None


def test_every_new_metric_reads_nothing_in_a_cell_without_the_mechanism():
    """Handed the GPT-2 serving configuration and the counters its
    adapter gives (what ``cellbench_tiny``'s cell hands every non-train
    metric), no ``.h1chat`` reader raises; without a device trace each
    returns None or a plain counter's value, and with one that lacks the
    family's kernels the family's own metrics still say nothing."""
    from cellbench import readers
    from cellbench.trace.reduce import Reduced

    bench, ctx = _readers(
        {"decode_steps": 100, "traced_steps": 10, "traced_kv_positions": 9,
         "traced_decode_tokens": 3, "slot_occupancy_pct": 50.0},
        [{"name": "serve.prefill", "attrs": {"padded_tokens": 64}}],
        config="gpt2-large-serve")
    ms = 10 ** 6
    red = Reduced({"tpu0": [["%fusion.1 = f32[8] fusion(...)", 0, ms]]},
                  0, 2 * ms)
    for reduced in (None, red):
        for name in H1_METRICS:
            m = next(m for m in bench.per_layer(COMMITTED)
                     if m["name"] == name + ".h1chat")
            value = readers.read(dict(m), dict(ctx, reduced=reduced),
                                 bench.custom_reader(m["name"]))
            if name == "slot_occupancy":
                assert value == 50.0
            elif reduced is None or name.startswith(("ssd_", "mfu")) \
                    or name in ("decode_attn", "decode_attn_roofline"):
                # no trace, or a trace without the family's kernels
                assert value is None, name


def test_the_chunked_scan_is_timed_by_the_innermost_loops_of_a_prefill():
    """``counts/ssd_prefill.scan_seconds``: in a prefill program the
    layer loop is a ``%while`` and each layer's chunked scan a
    ``%while`` inside it that holds no loop and no named kernel; the
    layer loop itself, the decode step's loop and a lone loop are not
    counted; a nested loop that holds a kernel is another program's,
    and nothing is reported."""
    from cellbench.trace.reduce import Reduced

    bench, ctx = _readers({}, [
        {"name": "serve.prefill", "attrs": {"padded_tokens": 2000}}])
    counts = bench.counts("ssd_prefill")
    ms = 10 ** 6
    prefill = ["jit_prefill(123)", 0, 50 * ms]
    step = ["jit_step(456)", 60 * ms, 20 * ms]
    layers = ["%while.316 = (s32[]) while(...)", ms, 40 * ms]
    scan = lambda t: [["%while.329 = (s32[], f32[8]) while(...)", t, 3 * ms],
                      ["%fusion.7 = f32[8,128,128] fusion(...)", t, ms]]
    flash = ["%apex_flash_fwd.3 = bf16[8] custom-call(", 2 * ms, ms]
    decode = ["%while.9 = (s32[]) while(...)", 61 * ms, 18 * ms]
    kernel = ["%apex_ssd_decode.11 = (f32[8]) custom-call(", 62 * ms, ms]
    events = [layers, flash, decode, kernel] + scan(4 * ms) + scan(20 * ms)
    red = Reduced({"tpu0": events}, 0, 100 * ms,
                  modules={"tpu0": [prefill, step]})
    assert counts.scan_seconds(red) == 0.006
    got = bench.custom_reader("ssd_prefill.h1chat").read(
        dict(ctx, reduced=red))
    assert math.isclose(got, 3.0)
    # a loop inside the decode step's loop is not a prefill's
    inner = ["%while.10 = (s32[]) while(...)", 63 * ms, ms]
    red = Reduced({"tpu0": events + [inner]}, 0, 100 * ms,
                  modules={"tpu0": [prefill, step]})
    assert counts.scan_seconds(red) == 0.006
    # another family's prefill: its nested loop holds a kernel
    held = ["%apex_kda_chunk_scan.19 = (f32[8]) custom-call(", 5 * ms, ms]
    red = Reduced({"tpu0": events + [held]}, 0, 100 * ms,
                  modules={"tpu0": [prefill, step]})
    notes = []
    assert counts.scan_seconds(red, notes) is None and notes
    # no nested loop, no program named: nothing
    flat = Reduced({"tpu0": [layers, flash]}, 0, 100 * ms,
                   modules={"tpu0": [prefill]})
    assert counts.scan_seconds(flat) is None
    assert counts.scan_seconds(Reduced({"tpu0": events}, 0, ms)) is None


def test_the_committed_configuration_is_the_catalog_row_cut_as_listed():
    """Every key of the catalog's ``config`` is in the file, unchanged
    unless ``changed`` lists it; ``changed``, ``reduced`` and
    ``published`` name the same two keys, neither a width; the bytes are
    the issue's arithmetic; the cell and its metrics are in
    ``BENCHMARK.json`` as the issue names them."""
    import sys

    sys.path.insert(0, str(REPO / "tests"))
    from test_serve_falcon_h1 import CATALOG

    conf = json.loads((REPO / "cellbench" / "configs"
                       / "falcon-h1-34b-serve-pp9.json").read_text())
    want = {"num_hidden_layers": (72, 8), "vocab_size": (261120, 65280)}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted(want)
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
        assert CATALOG[key] == published
    for key, value in CATALOG.items():
        if key not in want:
            assert conf[key] == value, key
    # the floors: a whole period and four layers, a quarter (an eighth
    # at least) of the vocabulary
    assert conf["num_hidden_layers"] >= 4
    assert conf["vocab_size"] * 4 == conf["published"]["vocab_size"]
    assert conf["deployment"]["stages"] * conf["num_hidden_layers"] == 72
    assert conf["deployment"]["chips_sharing_a_layer"] == 1
    assert conf["source"] == ("https://huggingface.co/tiiuae/"
                              "Falcon-H1-34B-Instruct/blob/main/config.json")
    for key in ("weights", "ssm_init", "leaf_names", "mup_vector", "state",
                "dtypes", "prefill"):
        assert conf["assumed"][key]
    # bytes at bfloat16, as the issue's arithmetic has them
    import jax
    from apex_tpu.models.falcon_h1 import param_shapes
    from cellbench.adapters.serve_falcon_h1 import decode_config, model_config

    cfg, dcfg = model_config(conf), decode_config(conf, 0)
    count = lambda tree: sum(math.prod(s) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = param_shapes(cfg)
    assert 0.859e9 < 2 * count(shapes["layers"]) / 8 < 0.861e9
    assert 8.21e9 < 2 * count(shapes) < 8.23e9
    spec = cfg.served_model().cache_spec()
    assert spec["k"] == spec["v"] == (8, 4, 128)
    assert (spec["ssm_state"].layers, spec["ssm_state"].shape) \
        == (8, (32, 128, 256))
    assert spec["ssm_conv"].shape == (3 * 5120,)
    assert (dcfg.max_batch, dcfg.cache.num_pages, dcfg.cache.page_size,
            dcfg.cache.pages_per_seq) == (96, 1024, 128, 20)
    assert dcfg.prefill_lengths == (128, 256, 512, 1024, 1536)
    # the cell, its traffic and its metrics
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == COMMITTED]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("falcon-h1-34b-serve-pp9", "h1chat-1.25knee", 1)
    entry = next(c for c in spec["configs"]
                 if c["name"] == "falcon-h1-34b-serve-pp9")
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    e2e = next(m for m in spec["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"][-1] == COMMITTED
    mine = [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [COMMITTED]]
    assert sorted(mine) == sorted(n + ".h1chat" for n in H1_METRICS)
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "h1chat-1.25knee.json").read_text())
    assert mix["generator"] == "open_loop_long"
    assert mix["in_flight_at_open"] == 96
    assert mix["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
        "max": 1536}
    assert mix["lengths"]["output"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.5, "min": 64,
        "max": 1024}
    assert math.isclose(mix["arrivals"]["rate"],
                        1.25 * mix["knee"]["requests_per_s"])


def test_the_state_number_reads_the_heads_of_longest_memory():
    """``adapter.slowest_heads``: the four heads whose decay rate a
    token, ``exp(A_log) softplus(dt_bias)``, is smallest, in head
    order; every head of a model with no more than four."""
    import numpy as np

    from cellbench.adapters import serve_falcon_h1 as adapter

    inv = lambda dt: np.log(np.expm1(dt))       # softplus^-1
    w0 = {"mamba.A_log": np.log([4.0, 1.0, 16.0, 2.0, 1.0, 8.0]),
          "mamba.dt_bias": inv(np.asarray([0.1, 0.1, 0.001, 0.001, 0.01,
                                           0.1]))}
    # rates 0.4, 0.1, 0.016, 0.002, 0.01, 0.8
    assert adapter.slowest_heads(w0).tolist() == [1, 2, 3, 4]
    few = {k: v[:3] for k, v in w0.items()}
    assert adapter.slowest_heads(few).tolist() == [0, 1, 2]
