"""A tiny cell of the ``afmoe`` training adapter
(``cellbench/adapters/train_afmoe.py``) through the harness on the CPU:
the run is judged ``correct`` against the plain reference
(``cellbench/reference/afmoe.py``), the float8 control and a step that
skips the bias update are not, the per-layer metrics that are counts
come out (a time never does on the CPU), and the committed
configuration is the catalog's row cut as its file says."""

import json

import pytest

from cellbench_tiny import REPO, make_root

from cellbench.run import run_cell

CELL = "tiny.moe8k"
MODEL = {
    "model_type": "afmoe", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 4,
    "published": {"num_experts": 16}, "num_shared_experts": 1,
    "num_experts_per_tok": 2, "route_scale": 2.826, "route_norm": True,
    "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "load_balance_coeff": 0.001, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "mup_enabled": True, "tie_word_embeddings": False,
    "cellbench": {
        "adapter": "train_afmoe", "held_start": 4,
        "args": {"seq": 64, "compute_dtype": "float32",
                 "param_dtype": "float32", "optimizer": "FusedAdam",
                 "use_buckets": False, "remat_policy": "full",
                 "flash_attention": True, "attn_impl": "interpret",
                 "fused_ce": True, "fused_ce_impl": "interpret",
                 "expert_impl": "interpret",
                 "betas": [0.9, 0.95], "eps": 1e-8, "weight_decay": 0.1},
        # float32 program against the float32 reference on the CPU,
        # readings over seeds 2**31+1..10 (sound) / +20..22 (float8) /
        # +30..31 (no bias update): a near tie of two experts' scores
        # flips in steps 2-3 (loss 1e-3 sound, 5e-3 float8: no limit
        # parts them); the first gradient does (difference 2.3e-3
        # against 0.31, norm 5e-4 against 0.02), as does the load of
        # step 1 (0 against 0.04) and, for a skipped bias update, the
        # bias (0.04 against 0.56)
        "correct": {"loss_abs": 5e-3, "grad_norm_gap": 5e-3,
                    "grad_diff": 2e-2, "grad_norm_gap_experts": 5e-3,
                    "grad_diff_experts": 2e-2, "delta_norm_gap": 2e-2,
                    "delta_norm_gap_experts": 2e-2, "load_share": 2e-2,
                    "bias_gap": 0.2, "held_count_gap": 3e-2}},
}
MIX = {"generator": "train_batches", "global_batch": 2, "tokens": "uniform",
       "lr": 1e-3, "prefetch": 2}
MOE8K = {"step_hbm.moe8k", "moe_tokens_per_expert.moe8k",
         "moe_buffer_fill.moe8k"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = make_root(tmp_path_factory.mktemp("bench"))
    data = tmp / "cellbench"
    (data / "configs" / "tiny-afmoe.json").write_text(json.dumps(MODEL))
    (data / "traffic" / "tiny-8k.json").write_text(json.dumps(MIX))
    spec = json.loads((tmp / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-afmoe", "source": "test",
        "file": "cellbench/configs/tiny-afmoe.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": CELL, "config": "tiny-afmoe", "traffic": "tiny-8k",
        "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"].endswith(".moe8k"):
            m["workloads"] = [CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.mark.parametrize("trace", [False, True])
def test_a_tiny_cell_runs_and_agrees_with_the_reference(root, trace):
    out = run_cell(root, CELL, 2 ** 31 + 77, 2.0, trace, require_tpu=False,
                   return_checks=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    names = [name for name, _, _ in out["checks"]]
    assert len(names) == 12 and sum("loss gap" in n for n in names) == 3
    for want in ("dense leaves", "expert leaves", "per-expert load",
                 "router bias", "assignments computed here"):
        assert any(want in n for n in names), want
    assert all(value <= limit for _, value, limit in out["checks"])
    if not trace:
        assert set(out["metrics"]) == {"setup_s"}   # a rate is no CPU number
        return
    got = out["metrics"]
    assert set(got) == MOE8K
    # 2 x 64 tokens x 2 a token over 16 experts: 16 a held expert a step
    # under even routing
    assert 4 < got["moe_tokens_per_expert.moe8k"]["value"] < 40
    assert 0 < got["moe_buffer_fill.moe8k"]["value"] <= 100


@pytest.mark.parametrize("control,failing", [
    ("float8_e4m3fn", "first-gradient difference"),
    ("no_balance_update", "router bias")])
def test_a_control_is_rejected(root, control, failing):
    out = run_cell(root, CELL, 2 ** 31 + 78, 1.0, False, require_tpu=False,
                   control=control, return_checks=True)
    bad = [name for name, value, limit in out["checks"] if value > limit]
    assert out["correct"] is False and any(failing in n for n in bad), bad
    if control == "no_balance_update":      # and nothing else moves
        assert all("router bias" in n for n in bad), bad


def test_the_readers_of_the_new_metrics_return_none_on_another_cell(root):
    """``cellbench_tiny.make_root`` hands every metric that does not end
    in ``.train`` to the tiny GPT-2 server: a reader of this family has
    to find nothing there, and say so without raising."""
    out = run_cell(root, "tiny.chat", 2 ** 31 + 79, 1.0, True,
                   require_tpu=False)
    assert out["correct"] is True
    assert not {m for m in out["metrics"] if m.endswith(".moe8k")} \
        - {"step_hbm.moe8k"}


def test_the_committed_configuration_is_the_published_one_cut_as_listed():
    """Every number of the catalog's row is in the file under its key,
    unchanged unless ``changed`` lists it; ``changed``, ``reduced`` and
    ``published`` name the same five keys; no width is among them; the
    file states the 128 experts, the deployment and every assumed
    point; and the step holds 705.5M parameters at 16 bytes."""
    conf = json.loads((REPO / "cellbench" / "configs"
                       / "trinity-mini-26b-a3b-train-ep8.json").read_text())
    want = {"num_hidden_layers": (32, 5), "num_dense_layers": (2, 1),
            "num_experts": (128, 16), "vocab_size": (200192, 25024)}
    assert sorted(conf["changed"]) == sorted(conf["reduced"]) \
        == sorted(conf["published"]) == sorted([*want, "layer_types"])
    for key, (published, here) in want.items():
        assert conf["published"][key] == published and conf[key] == here
    assert conf["layer_types"] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    same = {"global_attn_every_n_layers": 4, "head_dim": 128,
            "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "load_balance_coeff": 0.001,
            "max_position_embeddings": 131072, "model_type": "afmoe",
            "moe_intermediate_size": 1024, "mup_enabled": True,
            "n_group": 1, "num_attention_heads": 32,
            "num_expert_groups": 1, "num_experts_per_tok": 8,
            "num_key_value_heads": 4, "num_limited_groups": 1,
            "num_shared_experts": 1, "rms_norm_eps": 1e-05,
            "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
            "route_scale": 2.826, "score_func": "sigmoid",
            "sliding_window": 2048, "tie_word_embeddings": False,
            "topk_group": 1, "use_grouped_mm": True}
    for key, value in same.items():
        assert conf[key] == value, key
    assert conf["deployment"]["chips"] == 8
    assert "one chip of eight that share each layer" in \
        conf["deployment"]["layout"]
    assert set(conf["assumed"]) >= {"norms", "qk_norm", "output_gate",
                                    "rope", "window", "mup_enabled",
                                    "balance", "recipe", "weights"}
    import math

    import jax

    from apex_tpu.models.afmoe import param_shapes
    from cellbench.adapters.train_afmoe import program_config

    shapes = param_shapes(program_config(conf, conf["cellbench"]["args"]))
    shapes.pop("state")
    n = sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert 705.4e6 < n < 705.6e6 and 11.28e9 < 16 * n < 11.30e9
