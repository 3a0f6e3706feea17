"""Serve GPT with continuous batching — the one-command decode driver.

A load generator over :class:`apex_tpu.inference
.ContinuousBatchingScheduler`: N concurrent request streams with
Poisson arrivals, each a random prompt + generation budget, served by
the paged-KV decode engine.  Reports aggregate decode throughput
(tokens/sec) and per-token latency percentiles (p50/p99), plus
time-to-first-token — the serving numbers the north star is measured
by.

    python examples/gpt/serve_gpt.py --streams 8 --requests 32
    python examples/gpt/serve_gpt.py --smoke     # tiny CPU acceptance
    # the second family (latent attention + held experts), built from a
    # published-style config.json: bf16 weights, a one-pool latent cache
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/gigachat3.1-702b-a36b-serve-ep16.json \
        --streams 128 --page-size 128 --prompt-len 1024 --max-new 1024 \
        --prefill-buckets 256,512 --temperature 0
    # the same family with a mixer KIND per layer (kimi_linear: KDA
    # layers keep a per-slot recurrent state beside the latent pool)
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/kimi-linear-48b-a3b-serve-ep8.json \
        --streams 128 --page-size 128 --prompt-len 4096 --max-new 1024 \
        --prefill-buckets 512,1024,2048 --temperature 0
    # the third family (models/evabyte.py: EVA attention over a windowed
    # cache; the page size follows the file: window_size / chunk_size)
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/evabyte-6.5b-serve-pp4.json \
        --streams 20 --prompt-len 16384 --max-new 2048 \
        --prefill-buckets 2048,4096,8192 --temperature 0
    # the fifth family (models/falcon_h1.py: attention and a Mamba-2
    # mixer in every layer; paged K/V and a per-slot state side by side)
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/falcon-h1-34b-serve-pp9.json \
        --streams 96 --page-size 128 --prompt-len 1536 --max-new 1024 \
        --prefill-buckets 128,256,512,1024 --temperature 0
    # the sixth family (models/sdar_moe.py: generation by diffusion over
    # blocks of 4: a step forwards a block a slot and yields no token or
    # a whole block; --denoising-steps trades quality for speed)
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/sdar-30b-a3b-serve-ep8.json \
        --streams 64 --page-size 128 --prompt-len 768 --max-new 512 \
        --prefill-buckets 128,256,512 --temperature 0 --denoising-steps 2
    # the seventh family (models/lfm2_moe.py: layers that mix by a gated
    # short convolution OR by grouped attention, every expert held; paged
    # K/V over the attention layers, a per-slot tail over the others)
    python examples/gpt/serve_gpt.py --model-config \
        cellbench/configs/lfm2-8b-a1b-serve-pp2.json \
        --streams 256 --page-size 128 --prompt-len 1024 --max-new 2048 \
        --prefill-buckets 128,256,512 --temperature 0
    # serving v2: speculative decode + shared system prompt + chunked
    # prefill + a preemptible best-effort lane, one command
    python examples/gpt/serve_gpt.py --draft-len 4 --prefix-sharing \\
        --system-prompt-len 128 --prefill-chunk 64 --best-effort-frac 0.5

``--smoke`` runs a tiny greedy config end-to-end on CPU and ASSERTS
the engine's contracts: continuous batching admitted/evicted >= 3
generations through recycled pages, every generated token equals the
training forward's greedy continuation (decode↔training parity at the
decision level; the fp32 logits band lives in
tests/test_inference.py), and the decode step compiled exactly once
across all cache lengths and occupancies.

Weights are randomly initialized — this is a load/latency driver, not
a quality demo.  Kernel impls thread through flags (never env vars);
a kernel that dies at build time degrades once through
``resilience.fallback`` and the server keeps serving.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp

from apex_tpu.inference import (
    ContinuousBatchingScheduler, DecodeConfig, KVCacheConfig, Request,
)
from apex_tpu.models.gpt import GPTConfig, gpt_forward, init_params


def build_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="tiny deterministic CPU run asserting the "
                        "engine contracts (admit/evict, greedy parity, "
                        "compile-once)")
    p.add_argument("--streams", type=int, default=8,
                   help="decode slots (max concurrent sequences)")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson arrivals per second (0 = all queued "
                        "up front)")
    p.add_argument("--prompt-len", type=int, default=64,
                   help="max prompt length (per-request lengths are "
                        "uniform in [4, prompt-len])")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--hidden", type=int, default=768)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--kv-groups", type=int, default=None,
                   help="GQA query groups (None = MHA)")
    p.add_argument("--vocab", type=int, default=50304)
    p.add_argument("--model-config", default=None,
                   help="a published-style config.json instead of the "
                        "GPT flags above: the latent-attention, "
                        "sparse-expert family (model_type deepseek_v3, or "
                        "kimi_linear with its KDA layers; "
                        "models/mla_moe.py), model_type evabyte "
                        "(models/evabyte.py: --page-size then follows the "
                        "file, window_size / chunk_size, and prompts pad "
                        "to whole windows), model_type falcon_h1 "
                        "(models/falcon_h1.py), model_type lfm2_moe "
                        "(models/lfm2_moe.py), or model_type sdar_moe "
                        "(models/sdar_moe.py: generation by blocks; "
                        "--held-start picks the share of the experts).  "
                        "Where the file states the "
                        "router's width under 'published', its own "
                        "experts count is the number HELD here, from "
                        "--held-start on")
    p.add_argument("--held-start", type=int, default=0,
                   help="first expert id this process holds "
                        "(--model-config)")
    p.add_argument("--denoising-steps", type=int, default=None,
                   help="a block-generating model's denoising passes a "
                        "block, every request's (1 to its block length; "
                        "default: the model's): fewer is faster and worse")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated prompt pad lengths below "
                        "--prompt-len: one prefill compile each, a "
                        "prompt is padded to the shortest that holds it")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=None,
                   help="pool pages (default: sized for streams x "
                        "worst-case request + 1 garbage page)")
    p.add_argument("--kv-dtype", default="bfloat16",
                   choices=["bfloat16", "float32", "float16"])
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--attn-impl", default="auto",
                   choices=["auto", "pallas", "interpret", "xla"])
    p.add_argument("--sample-impl", default="auto",
                   choices=["auto", "pallas", "interpret", "xla"])
    p.add_argument("--seed", type=int, default=0)
    # ---- serving v2 (all default OFF: the plain PR 9 engine) ----
    p.add_argument("--draft-len", type=int, default=0,
                   help="speculative decode: n-gram drafts of up to k "
                        "tokens verified per step in ONE batched pass "
                        "(0 disables; the emitted stream is bitwise the "
                        "non-speculative stream)")
    p.add_argument("--ngram-max", type=int, default=3,
                   help="longest prompt-lookup n-gram the drafter sweeps")
    p.add_argument("--ngram-min", type=int, default=1)
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="chunked prefill: admit prompts as C-token "
                        "chunks interleaved with decode steps (any "
                        "prompt length; None = one padded prefill "
                        "shape, prompts capped at --prompt-len)")
    p.add_argument("--prefix-sharing", action="store_true",
                   help="dedupe identical prompt-prefix pages through "
                        "the refcounted trie, copy-on-write on first "
                        "divergence")
    p.add_argument("--system-prompt-len", type=int, default=0,
                   help="prepend one shared system prompt of this many "
                        "tokens to every request (the prefix-sharing "
                        "workload; 0 = fully random prompts)")
    p.add_argument("--best-effort-frac", type=float, default=0.0,
                   help="fraction of requests submitted on the "
                        "preemptible best_effort lane (the rest are "
                        "interactive); the report splits TTFT by lane")
    p.add_argument("--metrics-dir", default=None,
                   help="observability sink dir: serving metrics (queue "
                        "depth, slot/page occupancy, admission wait, "
                        "TTFT, inter-token latency histograms) land in "
                        "metrics.jsonl plus a final Prometheus snapshot "
                        "metrics.prom (apex_tpu.observability)")
    p.add_argument("--run-id", default="serve",
                   help="correlation id on metrics points and trace spans")
    p.add_argument("--replica-id", default=None,
                   help="this process's fleet replica id (the frontend's "
                        "roster name, e.g. r0).  Suffixes every "
                        "observability artifact — metrics_<id>.jsonl/"
                        ".prom, and <id> folded into --run-id for trace/"
                        "flight-recorder file names — so N replica "
                        "processes can share one --metrics-dir/"
                        "--trace-dir without clobbering each other "
                        "(the per-rank suffix convention, serving-side)")
    p.add_argument("--trace-dir", default=None,
                   help="host-side request tracing + crash forensics: "
                        "per-request spans (admission wait -> prefill "
                        "chunks -> decode/verify steps, spec accept "
                        "counts, split by lane; every span carries the "
                        "request's trace_id — the same id the TTFT/"
                        "inter-token histogram exemplars carry, so a "
                        "p99 outlier joins to its spans) exported as a "
                        "Perfetto-loadable trace_<run-id>_<pid>.json; "
                        "a flight recorder ring dumps here on a wedged "
                        "decode step")
    p.add_argument("--watchdog-secs", type=float, default=None,
                   help="serving step watchdog: a decode step exceeding "
                        "this many seconds (hung compile, wedged "
                        "collective) logs every queued/in-flight request "
                        "id (the requeue manifest), records "
                        "apex_serve_wedges_total, and exits 75 so a "
                        "supervisor restarts the engine")
    p.add_argument("--watchdog-compile-grace", type=float, default=600.0,
                   help="the FIRST step's watchdog allowance (the "
                        "prefill/decode jit compiles make it slow)")
    p.add_argument("--chaos-wedge-decode-step", type=int, default=None,
                   help="chaos: wedge this decode step's dispatch for "
                        "--chaos-wedge-secs (pair with --watchdog-secs)")
    p.add_argument("--chaos-wedge-secs", type=float, default=120.0)
    from apex_tpu.resilience.supervisor import add_supervisor_args

    add_supervisor_args(p)
    return p


def make_requests(args, rng):
    reqs, arrivals = [], []
    t = 0.0
    sysp = (rng.randint(0, args.vocab,
                        size=args.system_prompt_len).tolist()
            if args.system_prompt_len > 0 else [])
    for rid in range(args.requests):
        lo = min(4, args.prompt_len)
        plen = int(rng.randint(lo, args.prompt_len + 1))
        prompt = sysp + rng.randint(0, args.vocab, size=plen).tolist()
        lane = ("best_effort"
                if rng.uniform() < args.best_effort_frac else "interactive")
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=args.max_new, lane=lane,
                            denoising_steps=args.denoising_steps,
                            # the smoke checks every pass of a block-
                            # generating model (check_block_parity)
                            record_passes=args.smoke))
        if args.arrival_rate > 0:
            t += float(rng.exponential(1.0 / args.arrival_rate))
        arrivals.append(t)
    return reqs, arrivals


def serve(sched, reqs, arrivals):
    """Submit on (wall-clock) arrival, step until drained."""
    t0 = time.monotonic()
    pending = list(zip(arrivals, reqs))
    while pending or not sched.idle():
        now = time.monotonic() - t0
        while pending and pending[0][0] <= now:
            sched.submit(pending[0][1])
            pending.pop(0)
        if not sched.step() and pending:
            # nothing resident and the next arrival is in the future
            time.sleep(min(0.01, max(0.0, pending[0][0] - now)))
    return sched.completed


def report(completions, wall_secs):
    per_token, ttft = [], []
    lane_ttft = {}
    n_tokens = 0
    for c in completions:
        n_tokens += len(c.tokens)
        t = c.token_times[0] - c.submit_time
        ttft.append(t)
        lane_ttft.setdefault(c.lane, []).append(t)
        per_token.extend(np.diff(c.token_times))
    out = {
        "requests": len(completions),
        "generated_tokens": n_tokens,
        "wall_secs": round(wall_secs, 3),
        "tokens_per_sec": round(n_tokens / max(wall_secs, 1e-9), 2),
        "ttft_p50_ms": round(1e3 * float(np.percentile(ttft, 50)), 2),
        "ttft_p99_ms": round(1e3 * float(np.percentile(ttft, 99)), 2),
    }
    if len(lane_ttft) > 1:  # mixed lanes: the per-lane SLO evidence
        out["lanes"] = {
            lane: {"requests": len(ts),
                   "ttft_p50_ms": round(
                       1e3 * float(np.percentile(ts, 50)), 2),
                   "ttft_p99_ms": round(
                       1e3 * float(np.percentile(ts, 99)), 2)}
            for lane, ts in sorted(lane_ttft.items())}
    if per_token:
        out["per_token_p50_ms"] = round(
            1e3 * float(np.percentile(per_token, 50)), 2)
        out["per_token_p99_ms"] = round(
            1e3 * float(np.percentile(per_token, 99)), 2)
    return out


def check_block_parity(params, config, completions, max_check=3):
    """The smoke contract for a block-generating model: every position
    a pass unmasked took the full forward's greedy token (the mask's row
    apart) given the block's state before that pass, and a block was
    committed only once it held no mask."""
    from apex_tpu.models import sdar_moe

    W, mask = config.block_length, config.mask_id
    for c in completions[:max_check]:
        seq, state, at = list(c.prompt[:len(c.prompt) // W * W]), None, None
        for start, row in c.block_trace:
            if start != at:     # a new block: prompt remainder, then masks
                left = list(c.prompt[start:start + W])
                state, at = left + [mask] * (W - len(left)), start
            after = [int(t) for t in row[:W]]
            if row[W] == 1:     # the commit pass
                assert after == state and mask not in state, (c.rid, start)
                seq += state
                continue
            logits = sdar_moe.forward(params, jnp.asarray([seq + state]),
                                      config, attn_impl="xla")[0, start:]
            pred = jnp.argmax(logits.at[:, mask].set(-jnp.inf), axis=-1)
            for i in range(W):
                if after[i] != state[i]:
                    assert state[i] == mask and after[i] == int(pred[i]), (
                        f"rid={c.rid}: block {start} unmasked {after[i]} at "
                        f"{i} where the full forward's greedy token is "
                        f"{int(pred[i])}")
            state = after
        assert seq[len(c.prompt):len(c.prompt) + len(c.tokens)] == c.tokens


def check_greedy_parity(params, config, completions, max_check=3):
    """Every generated token must be the model's full forward's argmax
    continuation — the decision-level decode↔forward parity the smoke
    contract promises."""
    if type(config).__name__ == "SDARMoEConfig":
        return check_block_parity(params, config, completions, max_check)
    for c in completions[:max_check]:
        seq = list(c.prompt)
        for tok in c.tokens:
            if isinstance(config, GPTConfig):
                logits = gpt_forward(params, jnp.asarray([seq]), config)
                pred = int(jnp.argmax(logits[len(seq) - 1, 0]))
            elif type(config).__name__ == "EvaByteConfig":
                from apex_tpu.models import evabyte

                logits = evabyte.forward(params, jnp.asarray(seq), config,
                                         attn_impl="xla")
                pred = int(jnp.argmax(logits[-1, :config.vocab_size]))
            elif type(config).__name__ == "FalconH1Config":
                from apex_tpu.models import falcon_h1

                logits = falcon_h1.forward(params, jnp.asarray([seq]),
                                           config, attn_impl="xla")
                pred = int(jnp.argmax(logits[0, len(seq) - 1]))
            elif type(config).__name__ == "LFM2MoEConfig":
                from apex_tpu.models import lfm2_moe

                # causal: padding after the sequence moves nothing before
                # it, and one padded length is one compile of the scan
                padded = seq + [0] * (-len(seq) % 16)
                logits = jax.jit(
                    lfm2_moe.forward, static_argnames=("config", "attn_impl")
                )(params, jnp.asarray([padded]), config=config,
                  attn_impl="xla")
                pred = int(jnp.argmax(logits[0, len(seq) - 1]))
            else:
                from apex_tpu.models import mla_moe

                logits = mla_moe.forward(params, jnp.asarray([seq]), config,
                                         attn_impl="xla")
                pred = int(jnp.argmax(logits[0, len(seq) - 1]))
            assert pred == tok, (
                f"rid={c.rid}: decode produced {tok} where the full "
                f"forward's greedy continuation is {pred} at position "
                f"{len(seq)} — decode/forward parity broke")
            seq.append(tok)


def build_model(args, max_seq_len):
    """``(config, params)`` of the family the flags name: GPT from
    ``--layers/--hidden/--heads/...``, or — with ``--model-config`` — the
    family a published-style ``config.json`` names (``model_type``
    ``evabyte``: ``models/evabyte.py``; ``falcon_h1``:
    ``models/falcon_h1.py``; ``lfm2_moe``: ``models/lfm2_moe.py``;
    ``sdar_moe``: ``models/sdar_moe.py``; else the latent-attention,
    sparse-expert family; weights in bf16, random)."""
    key = jax.random.PRNGKey(args.seed)
    if args.model_config:
        from apex_tpu.models import (
            evabyte, falcon_h1, lfm2_moe, mla_moe, sdar_moe,
        )

        conf = json.loads(Path(args.model_config).read_text())
        dtype = jnp.float32 if args.smoke else jnp.bfloat16
        if conf.get("model_type") == "sdar_moe":
            # as below: the file's own count is what this process holds
            config = sdar_moe.SDARMoEConfig.from_published(
                conf, num_experts=conf.get("published", {}).get(
                    "num_experts", conf["num_experts"]),
                held_start=args.held_start, held_count=conf["num_experts"],
                param_dtype=dtype, compute_dtype=dtype)
            args.vocab = config.mask_id     # prompts hold no mask
            return config, sdar_moe.init_params(config, key)
        if conf.get("model_type") == "falcon_h1":
            config = falcon_h1.FalconH1Config.from_published(
                conf, param_dtype=dtype, compute_dtype=dtype)
            args.vocab = config.vocab_size
            return config, falcon_h1.init_params(config, key)
        if conf.get("model_type") == "lfm2_moe":
            config = lfm2_moe.LFM2MoEConfig.from_published(
                conf, param_dtype=dtype, compute_dtype=dtype)
            args.vocab = config.vocab_size
            return config, lfm2_moe.init_params(config, key)
        if conf.get("model_type") == "evabyte":
            config = evabyte.EvaByteConfig.from_published(
                conf, param_dtype=dtype, compute_dtype=dtype)
            args.vocab = config.vocab_size
            return config, evabyte.init_params(
                config, key, std=float(conf.get("init_std", 0.01275)))
        # a file cut to one chip's share states the router's width
        # under "published"; its own count is what this process holds
        name = ("n_routed_experts" if "n_routed_experts" in conf
                else "num_experts")
        config = mla_moe.MLAMoEConfig.from_published(
            conf,
            n_routed_experts=conf.get("published", {}).get(name, conf[name]),
            held_start=args.held_start, held_count=conf[name],
            param_dtype=dtype, compute_dtype=dtype)
        args.vocab = config.vocab_size
        return config, mla_moe.init_params(config, key)
    config = GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_layers=args.layers, num_attention_heads=args.heads,
        num_query_groups=args.kv_groups, max_seq_len=max_seq_len,
        position_embedding_type="rope",
        compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
        checkpoint_layers=False,
    )
    return config, init_params(config, key)


def build_scheduler(args, watchdog=None, anomaly=None):
    """The model, its paged cache and the scheduler exactly as ``main``
    serves them, from a parsed ``build_args()`` namespace: either
    family (:func:`build_model`) behind the one
    ``ContinuousBatchingScheduler``, which asks the model's config for
    its served-model adapter (cache spec, prefill, decode forward, head
    matrix, serving tree: docs/inference.md).  Returns ``(scheduler,
    params, config)``.  ``params`` is the tree AS BUILT (fp32 for GPT);
    the scheduler holds its own ``serving_params`` of it — the
    projection matrices cast once to the compute dtype — and no
    reference to this one, so the caller of this function is who still
    holds the fp32 matrices: a server that wants their memory back
    drops ``params`` (``main`` keeps it for ``--smoke``'s parity
    check).  ``chip_smoke.py`` drives its own request mixes through the
    schedulers this builds."""
    total_prompt = args.system_prompt_len + args.prompt_len
    config, params = build_model(
        args, max(total_prompt + args.max_new + args.draft_len + 1, 64))

    # a windowed cache (kv_cache.Windowed): a page of pooled columns is
    # one window, and a prompt is padded to whole windows
    from apex_tpu.inference.decode import served
    from apex_tpu.inference.kv_cache import Windowed

    windowed = next((e for e in served(config).cache_spec().values()
                     if isinstance(e, Windowed)), None)
    per_page, max_prompt = args.page_size, total_prompt
    if windowed is not None:
        args.page_size = windowed.window // windowed.stride
        per_page = windowed.window
        max_prompt = -(-total_prompt // per_page) * per_page
    # worst-case footprint: full prompt + generation budget + the
    # speculative write window (draft k/v land past the accepted stream)
    pages_per_seq = -(-(total_prompt + args.max_new + args.draft_len)
                      // per_page)
    num_pages = args.num_pages
    if num_pages is None:
        # pool sized so ~streams worst-case sequences fit (+ garbage
        # page); smaller pools exercise queueing, larger ones admission
        num_pages = 1 + args.streams * pages_per_seq
    dcfg = DecodeConfig(
        cache=KVCacheConfig(
            num_pages=num_pages, page_size=args.page_size,
            pages_per_seq=pages_per_seq,
            dtype=jnp.dtype(args.kv_dtype)),
        max_batch=args.streams, max_prompt_len=max_prompt,
        prefill_buckets=tuple(int(b) for b in
                              args.prefill_buckets.split(",") if b),
        temperature=args.temperature, top_k=args.top_k,
        attn_impl=args.attn_impl, sample_impl=args.sample_impl,
        sample_dot_dtype=jnp.float32 if args.smoke else None,
        base_seed=args.seed,
        draft_len=args.draft_len, ngram_max=args.ngram_max,
        ngram_min=args.ngram_min, prefill_chunk=args.prefill_chunk,
        prefix_sharing=args.prefix_sharing,
    )
    sched = ContinuousBatchingScheduler(params, config, dcfg,
                                        watchdog=watchdog, anomaly=anomaly)
    return sched, params, config


def main(argv=None):
    args = build_args().parse_args(argv)
    if args.supervise:
        # same self-healing outer loop as the trainer (no checkpoint
        # dir: a serving restart is stateless — the wedge manifest in
        # the logs is what a frontend replays)
        from apex_tpu.resilience.supervisor import run_supervised_cli

        return run_supervised_cli(
            args, argv=(None if argv is None else [__file__, *argv]),
            checkpoint_dir=None)

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        # tiny, deterministic, greedy: the CPU acceptance contract
        # (kernel impls stay as asked — a CPU caller passes "interpret")
        args.layers, args.hidden, args.heads = 2, 64, 4
        args.vocab = 128  # (--model-config brings its own)
        args.streams, args.requests, args.arrival_rate = 3, 7, 0.0
        args.prompt_len, args.max_new = 8, 4
        args.page_size, args.kv_dtype = 4, "float32"
        args.temperature, args.top_k = 0.0, 0

    from apex_tpu.observability import (
        AnomalyMonitor, get_metrics, set_step_context,
    )
    from apex_tpu.observability import flightrec, tracing
    from apex_tpu.resilience import ChaosMonkey, ChaosPlan, StepWatchdog

    # fleet-replica suffixing: N replica processes share one sink dir;
    # each writes metrics_<replica>.jsonl/.prom and folds the replica
    # id into the run id (trace + flight-recorder file names derive
    # from it) — same convention as pretrain's per-rank `_rank{p}`
    rep_sfx = f"_{args.replica_id}" if args.replica_id else ""
    if args.replica_id:
        args.run_id = f"{args.run_id}_{args.replica_id}"

    set_step_context(run_id=args.run_id, step=0)
    registry = get_metrics()  # the scheduler's gauges/histograms land here
    tracer = None
    if args.trace_dir:
        Path(args.trace_dir).mkdir(parents=True, exist_ok=True)
        tracer = tracing.configure()
    flight_dir = flightrec.default_dir(metrics_dir=args.metrics_dir,
                                       trace_dir=args.trace_dir)
    if flight_dir is not None:
        rec = flightrec.install(
            flightrec.FlightRecorder(flight_dir, run_id=args.run_id))
        if tracer is not None:
            rec.attach(tracer)
    # per-lane SLO burn: the scheduler scores every TTFT/inter-token
    # sample; alert counts ride the report so a lane claim carries its
    # alert evidence
    anomaly = (AnomalyMonitor()
               if (args.metrics_dir or args.trace_dir) else None)

    # wedged-decode-step watchdog: heartbeats ride scheduler.step(); a
    # wedge logs the queued/in-flight request ids and exits 75 for the
    # supervisor (no checkpointer to drain — serving state is the logs)
    watchdog = None
    if args.watchdog_secs is not None:
        watchdog = StepWatchdog(
            args.watchdog_secs,
            first_deadline_sec=args.watchdog_compile_grace)
        watchdog.start()
    monkey = None
    if args.chaos_wedge_decode_step is not None:
        monkey = ChaosMonkey(ChaosPlan.make(
            wedge_step_at=args.chaos_wedge_decode_step,
            wedge_step_seconds=args.chaos_wedge_secs))

    sched, params, config = build_scheduler(args, watchdog=watchdog,
                                            anomaly=anomaly)
    if not args.smoke:
        # only the smoke's parity check reads the tree as built; the
        # scheduler serves from its own (build_scheduler)
        params = None
    reqs, arrivals = make_requests(args, np.random.RandomState(args.seed))

    t0 = time.monotonic()
    if monkey is not None:
        with monkey.active():
            completions = serve(sched, reqs, arrivals)
    else:
        completions = serve(sched, reqs, arrivals)
    wall = time.monotonic() - t0
    if watchdog is not None:
        watchdog.stop()

    from apex_tpu.resilience.fallback import get_registry
    from apex_tpu.utils.platform import device_facts

    out = report(completions, wall)
    out["stats"] = dict(sched.stats)
    out["decode_compiles"] = sched.decode_cache_size()
    if sched.model.counter_names:
        # the model's device-side counters: one readback, after the run
        out["model_counters"] = sched.read_counters()
    # where the run is judged: the device it ran on and whether any
    # kernel degraded to its reference along the way
    out["device"] = device_facts()
    out["kernel_fallback"] = get_registry().status()
    if args.draft_len > 0:
        out["accepted_tokens_per_step"] = round(
            sched.stats["spec_emitted"]
            / max(sched.stats["spec_steps"], 1), 3)
    if args.prefix_sharing:
        full_per = args.system_prompt_len // args.page_size
        out["page_dedupe_ratio"] = round(
            sched.stats["shared_full_pages"]
            / max(len(completions) * full_per, 1), 3)
    if args.metrics_dir:
        mdir = Path(args.metrics_dir)
        mdir.mkdir(parents=True, exist_ok=True)
        registry.snapshot_jsonl(mdir / f"metrics{rep_sfx}.jsonl")
        (mdir / f"metrics{rep_sfx}.prom").write_text(
            registry.prometheus_text())
        out["metrics_dir"] = str(mdir)
    if anomaly is not None:
        anomaly.persist(args.metrics_dir or args.trace_dir)
        # per-lane alert counts: the SLO-lane evidence column
        out["anomaly"] = {"counts": anomaly.counts(),
                          "by_lane": anomaly.counts_by("lane")}
    if tracer is not None:
        out["trace_file"] = tracing.export_run(
            args.trace_dir, args.run_id, tracer)["chrome"]

    if args.smoke:
        assert len(completions) == args.requests, (
            f"served {len(completions)}/{args.requests}")
        assert sched.stats["evicted"] >= 3, (
            "smoke must admit/evict >= 3 generations through the pool")
        assert sched.stats["admitted"] > args.streams, (
            "smoke must recycle pages: more admissions than slots")
        assert out["decode_compiles"] == 1, (
            f"decode step compiled {out['decode_compiles']} times — "
            "the compile-once contract broke")
        check_greedy_parity(params, config, completions)
        out["smoke"] = True
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
