"""A tiny cell of the KDA/MLA adapter
(``cellbench/adapters/serve_kda_mla_moe.py``) through the harness on
the CPU, as ``test_cellbench_mla_moe.py`` drives the latent family's:
the run is judged ``correct`` against the plain reference, the window's
requests all finish, the per-layer metrics that are counts come out (a
time never does on the CPU), the float8 control is rejected, the
bfloat16-state control is rejected by the state number, and the
committed configuration is the catalog's row cut as it says."""

import json
import math

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.docgen"
MODEL = {
    "model_type": "kimi_linear", "vocab_size": 256,
    "model_max_length": 4096, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_shared_experts": 1, "num_experts": 8,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 16,
    "q_lora_rank": None, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "num_expert_group": 1, "topk_group": 1,
    "num_experts_per_token": 4, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "mla_use_nope": True,
    "linear_attn_config": {"full_attn_layers": [4],
                           "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "published": {"num_experts": 32},
    "cellbench": {
        "adapter": "serve_kda_mla_moe", "held_start": 8,
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "max_context": 128, "max_prompt_len": 96,
                 "prefill_buckets": [16, 32], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret",
                 "sample_dot_dtype": "float32"},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-4, "mean_logit_gap": 1e-5,
                    "kda_state_drift": 1e-4}},
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
    (data / "configs" / "tiny-kda.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-docgen.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-kda", "source": "test",
        "file": "cellbench/configs/tiny-kda.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-kda", "traffic": "tiny-docgen",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".docgen"):
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
    assert "first KDA layer's state" in state_name
    assert 0 < drift <= 1e-5 < drift_limit
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = out["metrics"]
    assert set(got) == {"slot_occupancy.docgen", "kv_pool_used.docgen",
                        "step_hbm.docgen", "moe_tokens_per_expert.docgen"}
    assert 0 < got["moe_tokens_per_expert.docgen"]["value"] <= 4
    assert 0 < got["slot_occupancy.docgen"]["value"] <= 100


def test_the_control_precision_is_rejected(root):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control="float8_e4m3fn", return_checks=True)
    (_, value, limit), (_, mean, mean_limit), (_, drift, drift_limit) \
        = out["checks"]
    assert value > limit and mean > mean_limit and drift > drift_limit
    assert out["correct"] is False


def test_a_bfloat16_state_is_rejected_and_by_the_state_number_alone(root):
    """The second control (``control="kda_state_bfloat16"``: the
    reference with its recurrent state rounded to bfloat16 after every
    token, in the program's place) through the harness's own judge.
    Rounding the state moves hardly an argmax, so the two numbers over
    the tokens CHOSEN do not see it; the third number, which reads the
    state itself, does, and the run comes out not correct (PERF.md,
    section 2, has the readings at the cell's size)."""
    out = run_cell(root, CELL, 2 ** 31 + 79, 1.0, False, require_tpu=False,
                   control="kda_state_bfloat16", return_checks=True)
    (name, value, _), (_, mean, _), (state_name, drift, drift_limit) \
        = out["checks"]
    assert "widest logit gap" in name and value < 0.05 and mean < 0.005
    assert "first KDA layer's state" in state_name
    assert drift > 1e-3 > drift_limit and out["correct"] is False


def test_the_state_controls_rounding_is_done_on_the_bits():
    """``reference._rounded``: bit for bit what a cast to bfloat16 and
    back gives (to nearest, ties to even, signed zeros, the largest
    finite values), but written with integer operations, so that no
    compiler can take it for excess precision and drop it (on the chip
    the cast pair did not round: PERF.md, section 6, PR 30)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench.reference import kda_mla_moe as reference

    rng = np.random.RandomState(0)
    x = rng.randn(100000).astype(np.float32) \
        * np.exp(rng.randn(100000) * 8).astype(np.float32)
    x = jnp.asarray(np.concatenate([x, np.asarray(
        [0.0, -0.0, 1.0, 1.00390625, 1.01171875, 3.0e38, -1e-30],
        np.float32)]))
    rounded = jax.jit(lambda x: reference._rounded(x, jnp.bfloat16))
    got, want = rounded(x), x.astype(jnp.bfloat16).astype(jnp.float32)
    assert bool(jnp.all(got == want)) and int(jnp.sum(got != x)) > 99000
    assert bool(jnp.all(jnp.signbit(got) == jnp.signbit(want)))
    text = rounded.lower(x).as_text()
    assert "bf16" not in text and "bitcast_convert" in text
    with pytest.raises(ValueError, match="exponent"):
        reference._rounded(x, jnp.float16)


def _readers(ctx_counters, spans):
    from cellbench.cells import Bench

    bench = Bench(REPO)
    conf = json.loads((REPO / "cellbench" / "configs"
                       / "kimi-linear-48b-a3b-serve-ep8.json").read_text())
    return bench, {"model": conf, "args": conf["cellbench"]["args"],
                   "counters": ctx_counters, "spans": spans,
                   "counts": bench.counts, "notes": []}


def test_the_new_kernels_work_is_counted_from_counters_and_spans():
    """``counts/kda_decode.py``: an update moves the state twice (read,
    write), scaled to the traced steps; ``counts/kda_prefill.py``: the
    traced prefills' padded tokens; both say nothing where the program
    has no such counter or span."""
    bench, ctx = _readers(
        {"decode_steps": 1000, "traced_steps": 100, "kda_layers": 10,
         "kda_state_updates": 1000 * 10 * 128},
        [{"name": "serve.prefill", "attrs": {"tokens": 700,
                                             "padded_tokens": 1024}},
         {"name": "serve.decode_step", "attrs": {}}])
    state = 32 * 128 * 128
    work = bench.counts("kda_decode").total(ctx)
    assert work["bytes"] == 2 * 4 * state * 100 * 10 * 128
    pre = bench.counts("kda_prefill").total(ctx)
    assert pre["bytes"] == 4 * (5 * 128 + 64) * 1024 * 10 * 32
    assert pre["flops"] == 2 * 128 * (3 * 128 + 64) * 1024 * 10 * 32
    _, bare = _readers({"decode_steps": 1000, "traced_steps": 100}, [])
    assert bench.counts("kda_decode").total(bare) is None
    assert bench.counts("kda_prefill").total(bare) is None
    for name in ("kda_decode_roofline", "kda_prefill", "kda_prefill_solve",
                 "kda_chunk_scan_roofline", "mla_decode_attn_roofline"):
        reader = bench.custom_reader(name + ".docgen")
        assert reader.read(dict(bare, reduced=None)) is None


def test_the_chunked_delta_rule_is_timed_by_the_loop_around_its_kernel():
    """``counts/kda_prefill.delta_rule_seconds``: the innermost
    ``%while`` around ``apex_kda_chunk_scan`` is one layer's chunked
    delta rule (solve and carry); the layer loop around it is not
    counted on top; a loop that holds another named kernel is not that
    loop, and nothing is reported; nor without any loop."""
    from cellbench.trace.reduce import Reduced

    bench, ctx = _readers({"kda_layers": 10}, [
        {"name": "serve.prefill", "attrs": {"padded_tokens": 2000}}])
    counts = bench.counts("kda_prefill")
    ms = 10 ** 6
    layer_loop = ["%while.316 = (s32[]) while(...)", 0, 40 * ms]
    rule = lambda t: [["%while.329 = (s32[], f32[8]) while(...)", t, 9 * ms],
                      ["%fusion.7 = f32[8,64,64] fusion(...)", t, 3 * ms],
                      ["%apex_kda_chunk_scan.19 = (f32[8]) custom-call(",
                       t + 3 * ms, 1 * ms],
                      ["%fusion.7 = f32[8,64,64] fusion(...)", t + 4 * ms,
                       3 * ms],
                      ["%apex_kda_chunk_scan.19 = (f32[8]) custom-call(",
                       t + 7 * ms, 1 * ms]]
    gmm = ["%gmm.4 = bf16[1024,1024] custom-call(", 30 * ms, 2 * ms]
    red = Reduced({"tpu0": [layer_loop, gmm] + rule(ms) + rule(15 * ms)},
                  0, 50 * ms)
    whole, kernel = counts.delta_rule_seconds(red)
    assert (whole, kernel) == (0.018, 0.004)
    ctx = dict(ctx, reduced=red)
    read = lambda name: bench.custom_reader(name + ".docgen").read(ctx)
    assert math.isclose(read("kda_prefill"), 9.0)
    assert math.isclose(read("kda_prefill_solve"), 7.0)
    # the compiler took the loop over head blocks apart: the layer loop
    # is the innermost around the kernel, and it holds the experts' too
    flat = Reduced({"tpu0": [layer_loop, gmm] + rule(ms)[1:]}, 0, 50 * ms)
    assert counts.delta_rule_seconds(flat) is None
    bare = Reduced({"tpu0": rule(ms)[1:]}, 0, 50 * ms)
    assert counts.delta_rule_seconds(bare) is None
    assert counts.delta_rule_seconds(Reduced({"tpu0": [gmm]}, 0, ms)) is None


def test_the_committed_configuration_is_the_catalog_row_cut_as_listed():
    """Every number of the catalog's ``config`` is in the file under its
    key, unchanged unless ``changed`` lists it; ``changed``, ``reduced``
    and ``published`` name the same four keys; no width is among them,
    nor inside the cut ``linear_attn_config``."""
    conf = json.loads((REPO / "cellbench" / "configs"
                       / "kimi-linear-48b-a3b-serve-ep8.json").read_text())
    want = {"num_hidden_layers": (27, 13), "num_experts": (256, 32),
            "vocab_size": (163840, 20480)}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted(list(want)
                                               + ["linear_attn_config"])
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
    catalog = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    for key, value in catalog.items():
        assert conf[key] == value, key
    lin, pub = conf["linear_attn_config"], \
        conf["published"]["linear_attn_config"]
    assert pub == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == pub[key]
    # the lists are the published ones up to layer 13: three whole
    # periods of the expert layers, 3 KDA to 1 MLA
    for key in ("kda_layers", "full_attn_layers"):
        assert lin[key] == [i for i in pub[key] if i <= 13]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) \
        == list(range(1, 14))
    assert conf["deployment"]["chips"] == 8 \
        and conf["deployment"]["stages"] == 2
    # bytes at bfloat16, as the issue's arithmetic has them: 6.92 GB
    from cellbench.adapters.serve_kda_mla_moe import model_config
    from apex_tpu.models.mla_moe import param_shapes
    import jax
    cfg = model_config(conf)
    n = sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert 6.85e9 < 2 * n < 6.95e9
    spec = cfg.served_model().cache_spec()
    state = spec["kda_state"]
    assert (state.layers, state.shape) == (10, (32, 128, 128))
    assert spec["kda_conv"].shape == (3 * 12288,)
    assert spec["latent"] == (3, 1, 576)
