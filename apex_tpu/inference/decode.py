"""Fused single-token decode step and the prefill step.

``make_decode_step`` builds ONE jitted function that advances every
resident sequence by one token: embedding lookup, all transformer
blocks (QKV projection, RoPE at each sequence's own position, paged
single-query attention, MLP — the block code shared with training via
:func:`apex_tpu.models.gpt.forward_decode`), and the fused sampling
head (logits → temperature/top-k → token in one kernel,
:mod:`apex_tpu.ops.decode_sampling_pallas` — the full-vocab fp32
softmax never reaches HBM).

Compile-once discipline: every input shape is static — the KV pools,
the (max_batch, pages_per_seq) page-table block, the per-slot scalar
arrays — and occupancy/length live in DATA (``active``, ``positions``),
so the step traces exactly once and serves every batch occupancy and
cache length from that one executable
(tests/test_lowered_invariants.py pins the trace count and that the
lowering has zero host transfers).  The pools donate — the caller
rebinds them every step — and every program here writes them IN PLACE
through one aliased Pallas call (``apex_kv_write``): no XLA op in a
step produces a pool-sized value, so the pool is held once and never
re-laid out (:mod:`apex_tpu.inference.kv_cache` has the why;
tests/test_tpu_bringup.py pins it on the compiled programs).

``make_prefill`` runs an admitted sequence's prompt through the
EXISTING training forward (``gpt_forward(return_kv=True)``) at one
static padded shape, writes the captured per-layer k/v into the
sequence's pages as page tiles, and samples the first generated token
from the last prompt position's hidden state.
"""

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.inference.kv_cache import (
    KVCacheConfig, alloc_pools, write_prompt_kv,
)
from apex_tpu.models.gpt import GPTConfig, forward_decode, gpt_forward
from apex_tpu.ops.decode_sampling_pallas import fused_sample

__all__ = [
    "DecodeConfig", "decode_logits_tokenwise", "make_decode_step",
    "make_prefill", "make_prefill_chunk", "make_sample_head",
    "make_verify_step",
]


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """Static serving configuration — everything here bakes into the
    compiled steps (thread impl choices HERE, never via env vars:
    the APX101/102 contract).

    ``max_batch``: decode-slot count (the step's batch dimension).
    ``max_prompt_len``: the prefill pad length (one prefill compile).
    ``temperature``/``top_k``: the sampling head; ``temperature=0`` is
    greedy argmax and ignores ``top_k``.
    ``attn_impl``/``sample_impl``: "auto" | "pallas" | "interpret" |
    "xla" for the decode-attention and sampling kernels (chosen
    impls degrade once through ``resilience.fallback``).
    ``sample_dot_dtype``: MXU dot dtype of the sampling head (None =
    the fused-CE default, bf16; tests pass fp32 for exact parity).

    Serving-v2 knobs (all default OFF — the PR 9 engine unchanged):
    ``draft_len`` k > 0 enables speculative decode (n-gram drafts of up
    to k tokens verified per step through the ``k + 1``-wide verify
    step); ``ngram_max``/``ngram_min`` bound the prompt-lookup n-gram
    sweep.  ``prefill_chunk`` C enables chunked prefill: prompts admit
    as C-token chunks interleaved with decode steps (ONE chunk compile
    per C, any prompt length up to the page-table capacity).
    ``prefix_sharing`` dedupes identical prompt-prefix pages through
    the refcounted trie (:mod:`apex_tpu.inference.prefix`) with
    copy-on-write on first divergence.
    """

    cache: KVCacheConfig = dataclasses.field(default_factory=KVCacheConfig)
    max_batch: int = 8
    max_prompt_len: int = 128
    temperature: float = 1.0
    top_k: int = 0
    attn_impl: str = "auto"
    sample_impl: str = "auto"
    sample_dot_dtype: Any = None
    base_seed: int = 0
    draft_len: int = 0
    ngram_max: int = 3
    ngram_min: int = 1
    prefill_chunk: Optional[int] = None
    prefix_sharing: bool = False

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0 (got {self.temperature}); "
                "0 means greedy")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (got {self.top_k})")
        if self.draft_len < 0:
            raise ValueError(f"draft_len must be >= 0 (got "
                             f"{self.draft_len}); 0 disables speculation")
        if not (1 <= self.ngram_min <= self.ngram_max):
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"({self.ngram_min}, {self.ngram_max})")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 (got "
                             f"{self.prefill_chunk}); None disables it")


def make_decode_step(config: GPTConfig, dcfg: DecodeConfig,
                     return_logits: bool = False):
    """Build the jitted one-token-per-sequence decode step.

    Returns ``step(params, pools, tokens, positions, active,
    page_tables, seeds) -> (pools, next_tokens)`` with

    - ``pools``: the ``{"k", "v"}`` page pools (DONATED — rebind on
      every call);
    - ``tokens``/``positions``/``active``: (B,) current token ids,
      their positions, slot liveness; inactive slots are fully masked
      (their cache writes land on the garbage page, their sampled
      token is meaningless);
    - ``page_tables``: (B, P) int32; ``seeds``: (B,) uint32 per-slot
      sampling counters.

    With ``return_logits=True`` the step instead returns
    ``(pools, logits)`` — the fp32 full-vocab head exactly as the
    training forward computes it — for the prefill↔decode parity band;
    serving never materializes those logits.
    """
    def step(params, pools, tokens, positions, active, page_tables, seeds):
        hidden, pools = forward_decode(
            params, tokens, positions, active, pools, page_tables,
            config, attn_impl=dcfg.attn_impl)
        if return_logits:
            logits = jnp.matmul(hidden.astype(jnp.float32),
                                params["embed"].T.astype(jnp.float32))
            return pools, logits
        next_tokens = fused_sample(
            hidden, params["embed"], seeds,
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return pools, next_tokens

    return jax.jit(step, donate_argnums=(1,))


def make_verify_step(config: GPTConfig, dcfg: DecodeConfig):
    """Build the jitted speculative VERIFY step — the decode step grown
    to ``W = draft_len + 1`` positions per slot, still compile-once.

    Returns ``verify(params, pools, tokens, positions, active,
    page_tables, seeds) -> (pools, sampled)`` where ``tokens`` is
    (B, W) int32 — column 0 the slot's current token (exactly the
    decode step's ``tokens``), columns 1..k its n-gram drafts —
    ``positions``/``active`` are (B,) as in the decode step, ``seeds``
    is (B, W) uint32 (one per prospective emission: the slot's NEXT W
    draw counters), and ``sampled`` is (B, W): the sampling head's
    token at every verified position.

    One batched pass scores all B*W positions through the paged
    attention kernel (each layer scatters the W rows' k/v, then every
    row attends under its own causal length — the fused-verification
    framing of arxiv 2502.17728) and ONE fused-sampling launch draws
    all W prospective tokens per slot.  The host accepts the longest
    prefix where ``sampled[:, j-1] == tokens[:, j]``
    (:func:`apex_tpu.inference.spec.accepted_tokens`); since
    ``sampled[i, j]`` is conditioned on a verified-correct prefix
    whenever it is consumed, the emitted stream is the NON-speculative
    stream — bitwise, including under temperature sampling (each
    emission spends the same (slot, draw) seed the plain decode step
    would).  A missed draft costs nothing extra: column 0 always
    yields the standard-path token.
    """
    W = dcfg.draft_len + 1

    def verify(params, pools, tokens, positions, active, page_tables,
               seeds):
        B = tokens.shape[0]
        off = jnp.arange(W, dtype=jnp.int32)
        pos_f = (positions.astype(jnp.int32)[:, None]
                 + off[None, :]).reshape(B * W)
        hidden, pools = forward_decode(
            params, tokens.reshape(B * W), pos_f,
            jnp.repeat(active, W), pools, page_tables, config,
            attn_impl=dcfg.attn_impl, verify_width=W)
        sampled = fused_sample(
            hidden, params["embed"], seeds.reshape(B * W),
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return pools, sampled.reshape(B, W)

    return jax.jit(verify, donate_argnums=(1,))


def make_prefill(config: GPTConfig, dcfg: DecodeConfig):
    """Build the jitted prompt-prefill step (one static padded shape).

    Returns ``prefill(params, pools, prompt, prompt_len, start,
    page_table_row, seed) -> (pools, first_token)`` where ``prompt``
    is (1, max_prompt_len) int32 (zero-padded past ``prompt_len``; the
    padded tail's k/v go to the garbage page and its causal rows are
    never read), ``start`` is the prefix-sharing write window (k/v for
    positions < ``start`` already live in shared pool pages and are
    NOT rewritten; 0 = unshared), ``page_table_row`` is the admitted
    sequence's (P,) table, and ``first_token`` is sampled from the
    LAST prompt position's hidden state with the same sampling head as
    decode.  Pools donate, as in the decode step.
    """
    S = dcfg.max_prompt_len

    def prefill(params, pools, prompt, prompt_len, start, page_table_row,
                seed):
        hidden, kv = gpt_forward(params, prompt, config,
                                 return_hidden=True, return_kv=True)
        k_stack, v_stack = kv  # (L, 1, KVH, S, hd)
        ks = k_stack[:, 0].transpose(0, 2, 1, 3)  # (L, S, KVH, hd)
        vs = v_stack[:, 0].transpose(0, 2, 1, 3)
        kp, vp = write_prompt_kv(pools["k"], pools["v"], ks, vs,
                                 page_table_row, prompt_len, start=start,
                                 impl=dcfg.attn_impl)
        h_last = hidden[jnp.clip(prompt_len - 1, 0, S - 1), 0]  # (H,)
        first = fused_sample(
            h_last[None], params["embed"], seed[None],
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return {"k": kp, "v": vp}, first[0]

    return jax.jit(prefill, donate_argnums=(1,))


def make_prefill_chunk(config: GPTConfig, dcfg: DecodeConfig):
    """Build the jitted chunked-prefill step: ONE compile per chunk
    size serves every prompt length.

    Returns ``chunk(params, pools, tokens, start_pos, valid,
    write_start, page_table_row) -> (pools, h_last)`` processing
    ``tokens`` (C,) — the prompt slice at absolute positions
    ``start_pos .. start_pos + C - 1``, of which the first ``valid``
    are real (the final chunk pads) — through the multi-position
    decode forward: each layer scatters the chunk's k/v into the
    sequence's pages, then every position attends causally over the
    WHOLE cached prefix (earlier chunks included) plus its intra-chunk
    predecessors.  ``write_start``: absolute positions below it skip
    the k/v scatter (shared-prefix pages, or a pure recompute pass
    over fully-cached positions).  ``h_last`` is the last valid
    position's pre-head hidden state — the sampling input once the
    final chunk lands (:func:`make_sample_head`).  Pools donate.

    Prompt length never touches a traced shape: arbitrarily long
    prompts are ``ceil(plen / C)`` calls of this one executable,
    interleavable with decode steps (the TTFT fix for resident
    streams).
    """
    C = int(dcfg.prefill_chunk)

    def chunk(params, pools, tokens, start_pos, valid, write_start,
              page_table_row):
        off = jnp.arange(C, dtype=jnp.int32)
        pos = start_pos.astype(jnp.int32) + off
        act = off < valid
        wmask = act & (pos >= write_start)
        hidden, pools = forward_decode(
            params, tokens, pos, act, pools, page_table_row[None],
            config, attn_impl=dcfg.attn_impl, verify_width=C,
            write_mask=wmask)
        h_last = hidden[jnp.clip(valid - 1, 0, C - 1)]
        return pools, h_last

    return jax.jit(chunk, donate_argnums=(1,))


def make_sample_head(config: GPTConfig, dcfg: DecodeConfig):
    """The standalone jitted sampling head — hidden (H,) + seed →
    token — used once per chunked admission (the final chunk returns
    ``h_last``; sampling stays OUT of the chunk step so intermediate
    chunks never pay the vocab matmul)."""
    del config  # the head is fully described by dcfg + params

    def head(params, hidden, seed):
        tok = fused_sample(
            hidden[None], params["embed"], seed[None],
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return tok[0]

    return jax.jit(head)


def decode_logits_tokenwise(params, config: GPTConfig, dcfg: DecodeConfig,
                            tokens, prefix: int, page_table_row):
    """The decode↔training parity probe: prefill ``tokens[:, :prefix]``
    through the training forward, then decode positions ``prefix..S-1``
    one token at a time through the jitted decode step
    (``return_logits=True``, ``dcfg``'s attention impl and cache dtype).

    ``tokens`` is (1, S); the sequence rides slot 0 of the
    ``dcfg.max_batch`` slots, the rest stay inactive.  Returns the
    (S - prefix, V) fp32 logits that
    ``gpt_forward(params, tokens, config)[prefix:, 0]`` must match — to
    reduction-reorder ulps in fp32, to the storage dtype's rounding with
    a bf16 cache (tests/test_inference.py; ``chip_smoke.py`` runs it on
    the compiled kernels)."""
    S = tokens.shape[1]
    B = dcfg.max_batch
    _, kv = jax.jit(
        lambda p, t: gpt_forward(p, t, config, return_kv=True))(
            params, tokens)
    ks = kv[0][:, 0].transpose(0, 2, 1, 3)[:, :prefix]  # (L, prefix, KVH, hd)
    vs = kv[1][:, 0].transpose(0, 2, 1, 3)[:, :prefix]
    pools = alloc_pools(config.num_layers, config.kv_heads, config.head_dim,
                        dcfg.cache)
    kp, vp = write_prompt_kv(pools["k"], pools["v"], ks, vs, page_table_row,
                             jnp.int32(prefix), impl=dcfg.attn_impl)
    pools = {"k": kp, "v": vp}
    step = make_decode_step(config, dcfg, return_logits=True)
    tables = jnp.zeros((B, page_table_row.shape[0]), jnp.int32) \
        .at[0].set(page_table_row)
    active = jnp.arange(B) == 0
    seeds = jnp.zeros((B,), jnp.uint32)
    out = []
    for pos in range(prefix, S):
        tok = jnp.zeros((B,), jnp.int32).at[0].set(tokens[0, pos])
        pools, logits = step(params, pools, tok,
                             jnp.full((B,), pos, jnp.int32), active,
                             tables, seeds)
        out.append(logits[0])
    return jnp.stack(out)
