"""A benchmark root of throw-away files for the tests: tiny
configurations (kernels through the Pallas interpreter, float32) and
short mixes, written into a temporary directory beside a copy of the
checked-in per-layer metrics and kernel counts.  Nothing here is
imported by the benchmark."""

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

MODEL = {"model_type": "gpt2", "vocab_size": 512, "n_positions": 64,
         "n_ctx": 64, "n_embd": 64, "n_layer": 2, "n_head": 4,
         "n_inner": None, "layer_norm_epsilon": 1e-5}
TRAIN = dict(MODEL, cellbench={
    "adapter": "train",
    "args": {"seq": 64, "compute_dtype": "float32",
             "param_dtype": "float32", "optimizer": "FusedAdam",
             "zero": False, "remat_policy": "full",
             "flash_attention": True, "fused_ce": True,
             "fused_ce_impl": "interpret", "betas": [0.9, 0.999],
             "eps": 1e-8, "weight_decay": 0.01},
    # float32 program against the float32 reference on the CPU: sound
    # runs read at most 8e-5 / 6e-4 / 5e-3 over seeds 1-12; the
    # float8 control reads 4e-3 on the loss
    "correct": {"loss_abs": 4e-4, "grad_norm_gap": 5e-3,
                "grad_diff": 5e-3, "delta_norm_gap": 5e-2}})
ZERO = json.loads(json.dumps(TRAIN))
ZERO["cellbench"]["args"].update(zero=True, optimizer="DistributedFusedAdam")
SERVE = dict(MODEL, cellbench={
    "adapter": "serve",
    "args": {"compute_dtype": "float32", "param_dtype": "float32",
             "kv_dtype": "float32", "max_batch": 4, "page_size": 8,
             "max_context": 64, "max_prompt_len": 32, "temperature": 0.0,
             "top_k": 0, "attn_impl": "interpret",
             "sample_impl": "interpret", "sample_dot_dtype": "float32"},
    "correct": {"logit_gap": 1e-4}})
STEADY = {"generator": "train_batches", "global_batch": 4,
          "tokens": "uniform", "lr": 1e-3, "prefetch": 2}
CHAT = {"generator": "open_loop",
        "arrivals": {"gaps": {"dist": "exponential"}, "rate": 6.0},
        "lengths": {"prompt": {"dist": "lognormal", "median": 12,
                               "sigma": 0.5, "min": 4, "max": 32},
                    "output": {"dist": "lognormal", "median": 8,
                               "sigma": 0.5, "min": 2, "max": 24}},
        "in_flight_at_open": 2}


def make_root(tmp: Path) -> Path:
    """Fill ``tmp`` with a BENCHMARK.json of two tiny cells that reuse
    every checked-in metric file by name."""
    data = tmp / "cellbench"
    for sub in ("configs", "traffic"):
        (data / sub).mkdir(parents=True)
    for sub in ("layer_metrics", "counts"):
        shutil.copytree(REPO / "cellbench" / sub, data / sub)
    (data / "configs" / "tiny-train.json").write_text(json.dumps(TRAIN))
    (data / "configs" / "tiny-serve.json").write_text(json.dumps(SERVE))
    (data / "configs" / "tiny-zero.json").write_text(json.dumps(ZERO))
    (data / "traffic" / "tiny-b4.json").write_text(json.dumps(STEADY))
    (data / "traffic" / "tiny-chat.json").write_text(json.dumps(CHAT))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": "tiny-train", "source": "test",
         "file": "cellbench/configs/tiny-train.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-serve", "source": "test",
         "file": "cellbench/configs/tiny-serve.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-zero", "source": "test",
         "file": "cellbench/configs/tiny-zero.json", "reduced": [],
         "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny.train", "config": "tiny-train",
         "traffic": "tiny-b4", "chips": 1, "why": "test"},
        {"name": "tiny.chat", "config": "tiny-serve",
         "traffic": "tiny-chat", "chips": 1, "why": "test"},
        {"name": "tiny.zero", "config": "tiny-zero",
         "traffic": "tiny-b4", "chips": 4, "why": "test"}]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.train", "tiny.zero"]
                              if "train" in m["name"] else ["tiny.chat"])
    for m in spec["per_layer"]:
        m["workloads"] = (["tiny.train", "tiny.zero"]
                          if m["name"].endswith(".train") else ["tiny.chat"])
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
