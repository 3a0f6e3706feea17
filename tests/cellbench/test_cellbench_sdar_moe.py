"""A tiny cell of the block-generation adapter
(``cellbench/adapters/serve_sdar_moe.py``) through the harness on the
CPU, as ``test_cellbench_falcon_h1.py`` drives that family's: the run is
judged ``correct`` against the plain reference on what every pass did,
the window's requests all finish, the per-layer metrics that are counts
come out (a time never does on the CPU), the float8 control is rejected,
the ``stale_block_kv`` control is rejected and by the logit numbers, the
``leftmost_unmask`` control by the confidence number alone, the new
counts and readers count what they say and say nothing to a cell
without the mechanism, and the committed configuration is the catalog's
row cut as it says."""

import json
import math

import numpy as np
import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.blockgen"
COMMITTED = "sdar-30b-a3b.serve-blockgen-over"
MODEL = {
    "model_type": "sdar_moe", "vocab_size": 64, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "num_experts": 2, "num_experts_per_tok": 2, "attention_bias": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "hidden_act": "silu",
    "norm_topk_prob": True, "rope_scaling": None, "rope_theta": 1000000,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 4096,
    "published": {"num_experts": 8, "vocab_size": 512},
    "cellbench": {
        "adapter": "serve_sdar_moe", "held_start": 2,
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "num_pages": 60, "max_context": 104, "max_prompt_len": 64,
                 "prefill_buckets": [16, 32], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret", "sample_dot_dtype": "float32",
                 "block_length": 4, "denoising_steps": 4,
                 "remasking": "low_confidence_static",
                 "confidence_threshold": 0.9, "mask_token_id": 63},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-3, "mean_logit_gap": 1e-4,
                    "confidence_gap": 1e-5}},
}
MIX = {"generator": "blockgen",
       "arrivals": {"gaps": {"dist": "exponential"}, "rate": 4.0},
       "lengths": {"prompt": {"dist": "lognormal", "median": 20,
                              "sigma": 0.8, "min": 3, "max": 60},
                   "output": {"dist": "lognormal", "median": 24,
                              "sigma": 0.5, "min": 8, "max": 40,
                              "multiple_of": 4},
                   "denoising_steps": [2, 4]},
       "in_flight_at_open": 3}
BLOCK_METRICS = ("decode_step", "tokens_per_forward", "commit_share",
                 "block_attn", "block_attn_roofline", "moe_experts",
                 "moe_experts_roofline", "moe_tokens_per_expert",
                 "prefill_share", "slot_occupancy", "kv_pool_used",
                 "step_hbm", "idle_in_call", "device_idle", "mfu",
                 "kv_write", "kv_write_roofline", "sched_host")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-sdar.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-blockgen.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-sdar", "source": "test",
        "file": "cellbench/configs/tiny-sdar.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-sdar", "traffic": "tiny-blockgen",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".blockgen"):
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
        (conf_name, gap, gap_limit) = out["checks"]
    assert "widest logit gap" in name and value <= limit
    assert "mean logit gap" in mean_name and mean <= mean_limit
    assert "confidence gap" in conf_name and gap <= gap_limit
    # the four checked requests' passes were read: two of 2 steps a
    # block, two of 4
    assert "of 4 requests" in name
    # the sound run's line says what a sampler that takes no notice of
    # the confidences would have read on the same passes: over the limit
    blind = float(conf_name.split("leftmost would read ")[1].rstrip(")"))
    assert blind > 100 * gap_limit
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == {n + ".blockgen" for n in (
        "tokens_per_forward", "commit_share", "moe_tokens_per_expert",
        "slot_occupancy", "kv_pool_used", "step_hbm")}
    # a block of 4 costs 3 or 5 passes: between 4/5 and 4/3 a pass, less
    # the first blocks' remainders and the last ones' surplus
    assert 0.5 < got["tokens_per_forward.blockgen"] < 4 / 3
    assert 100 / 5 <= got["commit_share.blockgen"] <= 100 / 2
    assert 0 < got["slot_occupancy.blockgen"] <= 100


def test_the_control_precision_is_rejected(root):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control="float8_e4m3fn", return_checks=True)
    (_, value, limit), (_, mean, mean_limit), _ = out["checks"]
    assert value > limit and mean > mean_limit
    assert out["correct"] is False


def test_stale_block_keys_are_rejected_and_by_the_logit_numbers(root):
    """The second control (``control="stale_block_kv"``: the float32
    reference in the program's place, every generated block's keys and
    values kept from its last denoising pass) through the harness's own
    judge: the tokens it unmasks in LATER blocks lie under the sound
    reference's best, the widest and the mean gap both fail."""
    out = run_cell(root, CELL, 2 ** 31 + 79, 1.0, False, require_tpu=False,
                   control="stale_block_kv", return_checks=True)
    (name, value, limit), (_, mean, mean_limit), _ = out["checks"]
    assert "widest logit gap" in name
    assert value > 100 * limit and mean > 100 * mean_limit
    assert out["correct"] is False


def test_a_sampler_blind_to_confidence_is_rejected_by_its_own_number(root):
    """The third control (``control="leftmost_unmask"``: the float32
    reference in the program's place, unmasking the leftmost masked
    positions whatever their confidence) through the harness's own
    judge: its tokens are the reference's own, so both logit numbers
    read 0, and ``confidence_gap`` alone fails."""
    out = run_cell(root, CELL, 2 ** 31 + 80, 1.0, False, require_tpu=False,
                   control="leftmost_unmask", return_checks=True)
    (_, value, _), (_, mean, _), (name, gap, limit) = out["checks"]
    assert value == 0 and mean == 0
    assert "confidence gap" in name and gap > 100 * limit
    assert out["correct"] is False


def test_a_trace_that_breaks_the_procedure_is_a_fault():
    """``adapter.passes_of``: the committed blocks are the served
    tokens, a pass rewrites no clean position, nothing follows a
    commit."""
    from cellbench.adapters import serve_sdar_moe as adapter

    M = 63
    prompt, tokens = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    trace = [(4, [5, M, 7, M, 0, 1]), (4, [5, 6, 7, 8, 0, 2]),
             (4, [5, 6, 7, 8, 1, 0]),
             (8, [M, 9, M, 10, 0, 2]), (8, [11, 9, 12, 10, 0, 2]),
             (8, [11, 9, 12, 10, 1, 0])]
    tokens = [6, 7, 8, 11, 9]
    taken = adapter.passes_of(prompt, tokens, 4, M, trace)
    assert taken["faults"] == []
    assert taken["clean"] == [1, 2, 3, 4, 5, 6, 7, 8, 11, 9, 12, 10]
    (a, first), (b, second) = taken["blocks"]
    assert (a, b) == (4, 8) and len(first) == len(second) == 2
    assert first[0] == ([5, M, M, M], [5, M, 7, M])
    bad = list(trace)
    bad[1] = (4, [5, 6, 9, 8, 0, 2])        # a clean position rewritten
    assert adapter.passes_of(prompt, tokens, 4, M, bad)["faults"]
    assert adapter.passes_of(prompt, tokens[:-1] + [0], 4, M,
                             trace)["faults"]
    assert adapter.passes_of(prompt, tokens, 4, M,
                             trace + [trace[-1]])["faults"]


def _readers(ctx_counters, spans, config="sdar-30b-a3b-serve-ep8"):
    from cellbench.cells import Bench

    bench = Bench(REPO)
    conf = json.loads((REPO / "cellbench" / "configs"
                       / f"{config}.json").read_text())
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "blockgen-1.25knee.json").read_text())
    return bench, {"model": conf, "args": conf["cellbench"]["args"],
                   "counters": ctx_counters, "spans": spans,
                   "traffic": mix, "chips": 1, "reduced": None,
                   "peaks": bench.peaks("TPU v5 lite"),
                   "counts": bench.counts, "notes": []}


def test_the_block_steps_work_is_counted_from_the_programs_counters():
    """``counts/block_decode_attention.py``: a column is 2,048 B a layer,
    read ONCE a slot a step, 4 operations a query head's value a ROW of
    the block; ``counts/sdar_moe_model.py``: a row multiplies 19.1 M
    weights a layer outside the experts; the counter readers divide what
    they say; all say nothing where the counters are not there."""
    c = {"decode_steps": 1000, "traced_steps": 100, "window_s": 50.0,
         "window_tokens": 150000, "blk_rows_forwarded": 1000 * 256,
         "blk_denoise_passes": 48000, "blk_commit_passes": 16000,
         "blk_tokens_unmasked": 160000, "blk_kv_cols": 1000 * 64 * 500,
         "moe_assignments_held": 1000 * 48 * 256, "moe_layers": 48,
         "moe_assignments_all": 1000 * 48 * 256 * 8, "experts_held": 16,
         "moe_experts_hit": 1000 * 48 * 16}
    bench, ctx = _readers(c, [])
    attn = bench.counts("block_decode_attention").total(ctx)
    cols = 100 * 64 * 500
    assert attn["bytes"] == 2048 * 48 * cols
    assert attn["flops"] == 4 * 32 * 128 * 48 * 4 * cols
    # a pass owes its block's 4 columns a layer, whatever tile moves
    write = bench.counts("block_kv_write").total(ctx)
    assert write == {"flops": 0.0, "bytes": 2048 * 48 * 4 * 6400}
    model = bench.counts("sdar_moe_model")
    assert model.layer_row_weights(ctx["model"]) \
        == 2048 * 5120 + 4096 * 2048 + 2048 * 128 == 19136512
    want = (256000 * 48 * 2 * 19136512
            + c["moe_assignments_held"] * 2 * 3 * 2048 * 768
            + 4 * 32 * 128 * 48 * 4 * c["blk_kv_cols"]
            + 48000 * 4 * 2 * 18992 * 2048)
    assert model.flops(ctx["model"], c) == want
    mfu = bench.custom_reader("mfu.blockgen").read(ctx)
    assert math.isclose(mfu, want / 50.0 / 197e12 * 100) and 1 < mfu < 30
    read = lambda n: bench.custom_reader(n + ".blockgen").read(ctx)
    assert read("tokens_per_forward") == 150000 / 64000
    assert read("commit_share") == 25.0
    assert read("moe_tokens_per_expert") == 16.0
    _, bare = _readers({"decode_steps": 1000, "traced_steps": 100}, [])
    assert bench.counts("block_decode_attention").total(bare) is None
    assert bench.counts("block_kv_write").total(bare) is None
    assert model.flops(bare["model"], bare["counters"]) is None
    for name in ("tokens_per_forward", "commit_share", "mfu",
                 "block_attn_roofline", "kv_write_roofline",
                 "moe_tokens_per_expert"):
        assert bench.custom_reader(name + ".blockgen").read(bare) is None


def test_every_new_metric_reads_nothing_in_a_cell_without_the_mechanism():
    """Handed the GPT-2 serving configuration and the counters its
    adapter gives, no ``.blockgen`` reader raises; without a device
    trace each returns None or a plain counter's value, and with one
    that lacks the family's kernels and counters the family's own
    metrics still say nothing."""
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
        for name in BLOCK_METRICS:
            m = next(m for m in bench.per_layer(COMMITTED)
                     if m["name"] == name + ".blockgen")
            value = readers.read(dict(m), dict(ctx, reduced=reduced),
                                 bench.custom_reader(m["name"]))
            if name == "slot_occupancy":
                assert value == 50.0
            elif reduced is None or name.startswith(("block_", "moe_", "mfu",
                                                     "tokens_", "commit_",
                                                     "kv_write")):
                assert value is None, name


def test_the_generator_deals_steps_and_whole_blocks():
    """``generators/blockgen.py``: answers of whole blocks, ids below
    the mask's row, the mix's steps in equal shares dealt by the seed,
    the same multiset of lengths for every seed and ONE in-flight set."""
    from cellbench import loadgen

    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "blockgen-1.25knee.json").read_text())
    gen = loadgen.generator(mix)
    a, b = (gen.requests(mix, 18992, s, 51.0) for s in (2 ** 31 + 5, 9))
    assert len(a) == len(b) == round(51 * mix["arrivals"]["rate"])
    for r in a:
        assert r.max_new_tokens % 4 == 0 and 64 <= r.max_new_tokens <= 512
        assert 32 <= len(r.prompt) <= 768 and max(r.prompt) < 18991
    lengths = lambda rs: (sorted(len(r.prompt) for r in rs),
                          sorted(r.max_new_tokens for r in rs))
    assert lengths(a) == lengths(b) and a[0].prompt != b[0].prompt
    steps = gen.steps(mix, len(a), 7)
    assert sorted(set(steps)) == [2, 4]
    assert abs(steps.count(2) - steps.count(4)) <= 1
    assert steps != gen.steps(mix, len(a), 8)
    held_a, held_b = (gen.in_flight_at_open(mix, 18992, s) for s in (1, 2))
    assert len(held_a) == 64 and lengths(held_a) == lengths(held_b)
    assert [len(r.prompt) for r in held_a] == [len(r.prompt) for r in held_b]


def test_the_committed_configuration_is_the_catalog_row_cut_as_listed():
    """Every key of the catalog's ``config`` is in the file, unchanged
    unless ``changed`` lists it; ``changed``, ``reduced`` and
    ``published`` name the same two keys, neither a width; the bytes are
    the issue's arithmetic; the cell and its metrics are in
    ``BENCHMARK.json`` as the issue names them."""
    import sys

    sys.path.insert(0, str(REPO / "tests"))
    from test_serve_sdar_moe import CATALOG

    conf = json.loads((REPO / "cellbench" / "configs"
                       / "sdar-30b-a3b-serve-ep8.json").read_text())
    want = {"num_experts": (128, 16), "vocab_size": (151936, 18992)}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted(want)
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
        assert CATALOG[key] == published
    for key, value in CATALOG.items():
        if key not in want:
            assert conf[key] == value, key
    # the floors: all 48 layers, 8 routed experts at least, an eighth of
    # the vocabulary
    assert conf["num_hidden_layers"] == 48 and conf["num_experts"] >= 8
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"]
    assert conf["deployment"]["chips_sharing_a_layer"] == 8
    assert conf["deployment"]["experts_a_chip"] * 8 == 128
    assert conf["source"] == ("https://huggingface.co/JetLM/"
                              "SDAR-30B-A3B-Chat/blob/main/config.json")
    for key in ("block_length", "denoising_steps", "strategy", "no_shift",
                "q_k_norm", "mask_token", "commit_pass", "dtypes", "slots",
                "weights", "correct"):
        assert conf["assumed"][key]
    # bytes at bfloat16, as the issue's arithmetic has them
    import jax
    from apex_tpu.models.sdar_moe import param_shapes
    from cellbench.adapters.serve_sdar_moe import decode_config, model_config

    cfg, dcfg = model_config(conf), decode_config(conf, 0)
    count = lambda tree: sum(math.prod(s) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    shapes = param_shapes(cfg)
    assert count(shapes["layers"]) == 48 * 94638336
    # the issue's 4,620,431,360 and the final norm's gain
    assert count(shapes) == 4620431360 + 2048
    assert (cfg.held, cfg.num_experts, cfg.mask_id, cfg.block_length) \
        == (range(0, 16), 128, 18991, 4)
    spec = cfg.served_model().cache_spec()
    assert spec["k"] == spec["v"] == (48, 4, 128)
    assert (dcfg.max_batch, dcfg.cache.num_pages, dcfg.cache.page_size,
            dcfg.cache.pages_per_seq) == (64, 320, 128, 10)
    assert dcfg.prefill_lengths == (128, 256, 512, 768)
    # the cell, its traffic and its metrics
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == COMMITTED]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("sdar-30b-a3b-serve-ep8", "blockgen-1.25knee", 1)
    entry = next(c for c in spec["configs"]
                 if c["name"] == "sdar-30b-a3b-serve-ep8")
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"]
    e2e = next(m for m in spec["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert COMMITTED in e2e["workloads"]    # not "the last": the next
    # cell is appended after it (test_cellbench_falcon_h1.py holds ITS
    # cell to the last place, which this cell's entry ended)
    mine = [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [COMMITTED]]
    assert sorted(mine) == sorted(n + ".blockgen" for n in BLOCK_METRICS)
    mix = json.loads((REPO / "cellbench" / "traffic"
                      / "blockgen-1.25knee.json").read_text())
    assert mix["generator"] == "blockgen"
    assert mix["in_flight_at_open"] == 64
    assert mix["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.8, "min": 32,
        "max": 768}
    assert mix["lengths"]["output"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
        "max": 512, "multiple_of": 4}
    assert mix["lengths"]["denoising_steps"] == [2, 4]
    assert math.isclose(mix["arrivals"]["rate"],
                        1.25 * mix["knee"]["requests_per_s"])
