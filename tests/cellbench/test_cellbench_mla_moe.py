"""A tiny cell of the latent-attention, sparse-expert adapter
(``cellbench/adapters/serve_mla_moe.py``) through the harness on the
CPU, as ``test_cellbench_harness.py`` drives the GPT-2 adapters: the
run is judged ``correct`` against the plain reference, the window's
requests all finish, and the per-layer metrics that are counts come
out (a time never does on the CPU)."""

import json

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.longgen"
MODEL = {
    "model_type": "deepseek_v3", "vocab_size": 256,
    "max_position_embeddings": 4096, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_nextn_predict_layers": 0,
    "num_attention_heads": 4, "n_shared_experts": 1,
    "n_routed_experts": 8, "routed_scaling_factor": 2.5,
    "kv_lora_rank": 16, "q_lora_rank": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "qk_nope_head_dim": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "first_k_dense_replace": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "rope_type": "yarn"},
    "published": {"n_routed_experts": 32},
    "cellbench": {
        "adapter": "serve_mla_moe", "held_start": 8,
        "args": {"compute_dtype": "float32", "param_dtype": "float32",
                 "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
                 "max_context": 64, "max_prompt_len": 32,
                 "prefill_buckets": [8, 16], "temperature": 0.0,
                 "top_k": 0, "attn_impl": "interpret",
                 "sample_impl": "interpret",
                 "sample_dot_dtype": "float32"},
        # float32 program against the float32 reference: 0 or rounding
        "correct": {"logit_gap": 1e-4, "mean_logit_gap": 1e-5}},
}
MIX = {"generator": "open_loop_long",
       "arrivals": {"gaps": {"dist": "exponential"}, "rate": 5.0},
       "lengths": {"prompt": {"dist": "lognormal", "median": 10,
                              "sigma": 0.6, "min": 3, "max": 32},
                   "output": {"dist": "lognormal", "median": 10,
                              "sigma": 0.5, "min": 4, "max": 24}},
       "in_flight_at_open": 3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-mla-moe.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-longgen.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-mla-moe", "source": "test",
        "file": "cellbench/configs/tiny-mla-moe.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-mla-moe", "traffic": "tiny-longgen",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".longgen"):
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.mark.parametrize("trace", [False, True])
def test_a_tiny_cell_runs_and_agrees_with_the_reference(root, trace):
    out = run_cell(root, CELL, 2 ** 31 + 77, 2.0, trace, require_tpu=False,
                   return_checks=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 10 and out["device"]["platform"] == "cpu"
    (name, value, limit), (mean_name, mean, mean_limit) = out["checks"]
    assert "widest logit gap" in name and value <= limit
    assert "mean logit gap" in mean_name and mean <= mean_limit
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = out["metrics"]
    assert set(got) == {"slot_occupancy.longgen", "kv_pool_used.longgen",
                        "step_hbm.longgen", "moe_tokens_per_expert.longgen"}
    assert 0 < got["moe_tokens_per_expert.longgen"]["value"] <= 4
    assert 0 < got["slot_occupancy.longgen"]["value"] <= 100


def test_the_control_precision_is_rejected(root):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control="float8_e4m3fn", return_checks=True)
    (_, value, limit), (_, mean, mean_limit) = out["checks"]
    assert value > limit and mean > mean_limit and out["correct"] is False


def test_the_committed_configuration_is_the_published_one_cut_as_listed():
    """Every number of the published config is in the file under its
    key, unchanged unless ``changed`` lists it; ``changed``, ``reduced``
    and ``published`` name the same five keys; no width is among them."""
    conf = json.loads((REPO / "cellbench" / "configs"
                       / "gigachat3.1-702b-a36b-serve-ep16.json").read_text())
    want = {"num_hidden_layers": (64, 6), "first_k_dense_replace": (3, 1),
            "n_routed_experts": (256, 16), "vocab_size": (128256, 16032),
            "num_nextn_predict_layers": (1, 0)}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted(want)
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
    widths = {"hidden_size": 7168, "intermediate_size": 18432,
              "moe_intermediate_size": 2048, "num_attention_heads": 64,
              "kv_lora_rank": 512, "q_lora_rank": 1536,
              "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
              "v_head_dim": 192, "num_experts_per_tok": 8, "n_group": 8,
              "topk_group": 4, "routed_scaling_factor": 2.5,
              "n_shared_experts": 1, "rope_theta": 100000,
              "max_position_embeddings": 262144}
    for key, value in widths.items():
        assert conf[key] == value, key
    assert conf["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn"}
    assert conf["deployment"]["chips"] == 16
    # bytes at bfloat16, as the issue's arithmetic has them
    from cellbench.adapters.serve_mla_moe import model_config
    from apex_tpu.models.mla_moe import param_shapes
    import jax
    import math
    n = sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(model_config(conf)),
        is_leaf=lambda x: isinstance(x, tuple)))
    assert 10.30e9 < 2 * n < 10.40e9
