"""The compiled steps of the serve loop: the fused single-token decode
step, the speculative verify step, the block step of a model that
generates by diffusion over blocks, and the prefill step.

The steps are built for a **served model**: any object with
``cache_spec()`` (cache entry name -> ``(layers, heads, dim)`` for a
paged pool or a :class:`~apex_tpu.inference.kv_cache.PerSlot` for
per-slot recurrent state), ``prefill``,
``decode``, ``head(params)`` (the (V, H) matrix the sampling head
multiplies), ``serving_params(params)`` (the tree the steps should be
given: :func:`cast_once`), ``multi_position``, ``counter_names`` and
``max_positions`` — or a model configuration whose ``served_model()``
gives one (:func:`served`).  ``models.gpt.GPTServed`` is the first,
``models.mla_moe.MLAMoEServed`` (a latent cache, held experts) the
second, ``models.falcon_h1.FalconH1Served`` (paged K/V AND per-slot
state in every layer) the fifth, ``models.sdar_moe.SDARMoEServed`` (a
block a slot a step: it declares ``block_length`` and ``mask_id`` and
brings ``decode_block``) the sixth; nothing here imports a model
(docs/inference.md has the interface).

There are THREE kinds of step.  The plain decode step advances every
slot by one position and yields one token a slot; the verify step
scores ``draft_len + 1`` consecutive positions a slot, each under its
own causal length, and yields one to ``draft_len + 1`` tokens; the
block step (:func:`make_block_step`) forwards a slot's open BLOCK of
``block_length`` positions, which all see the same columns (a denoising
pass: no token), and beside it the clean block before it, whose commit
rides that pass and yields the block's tokens.

``make_decode_step`` builds ONE jitted function that advances every
resident sequence by one token: embedding lookup, all transformer
blocks (projections, RoPE at each sequence's own position, paged
single-query attention, FFN — the block code shared with the model's
full forward, e.g. :func:`apex_tpu.models.gpt.forward_decode`), and the
fused sampling head (logits → temperature/top-k → token in one kernel,
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
model's full forward (GPT: the training forward,
``gpt_forward(return_kv=True)``) at a static padded shape — ONE
(``max_prompt_len``) or the few of ``prefill_buckets`` — writes the
captured per-layer cache columns into the sequence's pages as page
tiles, and samples the first generated token from the last prompt
position's hidden state.
"""

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.inference.kv_cache import (
    KVCacheConfig, alloc_named_pools, per_slot_names, windowed_entry,
    write_prompt_pools, write_prompt_windowed,
)
from apex_tpu.observability import tracing as _tracing
from apex_tpu.ops.decode_sampling_pallas import (
    fused_sample, fused_sample_confidence,
)
# the install of a slot's rows: every per-slot entry's, whichever
# recurrence keeps it (ops/kda.py's KDA, ops/ssd.py's Mamba-2)
from apex_tpu.ops.kda import install_rows

__all__ = [
    "BLOCK_COMMIT", "BLOCK_DENOISE", "BLOCK_IDLE", "DecodeConfig",
    "block_passes", "cast_once", "decode_logits_tokenwise",
    "init_block_state", "make_block_prefill", "make_block_step",
    "make_decode_step", "make_prefill", "make_prefill_chunk",
    "make_sample_head", "make_verify_step", "served", "unmask_counts",
]


def served(model):
    """The served model: ``model`` itself, or — given a model
    configuration — what its ``served_model()`` builds."""
    return model.served_model() if hasattr(model, "served_model") else model


@partial(jax.jit, static_argnames="dtype")
def _cast_leaves(leaves, dtype):
    return [x.astype(dtype) for x in leaves]


def cast_once(params, names, dtype):
    """``params`` (nested dicts) with every leaf whose own key is in
    ``names`` cast to ``dtype`` — what a served model's
    ``serving_params`` returns.

    A family names the leaves that EVERY one of its served programs
    reads only as ``leaf.astype(compute_dtype)``: handed this tree,
    those casts are no-ops and the programs multiply the very bits they
    rounded to before, but the rounding is done once and not in every
    decode step and every prefill (XLA hoists a stacked matrix's cast
    out of the layer scan and runs it once a PROGRAM: 61% of the device
    time of GPT-2 large's serving cells; PERF.md, PR 29).

    All casts are ONE jitted program, finished on return.  A picked
    leaf that already has ``dtype`` is left alone, and with none to
    cast ``params`` itself comes back and nothing is launched: a tree
    prepared before, a checkpoint in the compute dtype and
    ``compute_dtype=float32`` pass through.  The span
    ``serve.prepare_params`` says what happened: ``cast_leaves``,
    ``cast_bytes`` (read, in the leaves' old dtype) and ``kept_bytes``
    (every other leaf)."""
    dtype = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in flat]
    todo = [i for i, (path, leaf) in enumerate(flat)
            if path[-1].key in names and leaf.dtype != dtype]
    nbytes = [leaf.size * leaf.dtype.itemsize for leaf in leaves]
    cast_bytes = sum(nbytes[i] for i in todo)
    with _tracing.span("serve.prepare_params", cast_leaves=len(todo),
                       cast_bytes=cast_bytes,
                       kept_bytes=sum(nbytes) - cast_bytes):
        if not todo:
            return params
        cast = jax.block_until_ready(
            _cast_leaves([leaves[i] for i in todo], dtype))
    for i, x in zip(todo, cast):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """Static serving configuration — everything here bakes into the
    compiled steps (thread impl choices HERE, never via env vars:
    the APX101/102 contract).

    ``max_batch``: decode-slot count (the step's batch dimension).
    ``max_prompt_len``: the prefill pad length (one prefill compile).
    ``prefill_buckets``: further, shorter pad lengths: a prompt is
    padded to the shortest of them (or ``max_prompt_len``) that holds
    it — one prefill compile a length, for prompt mixes whose median is
    a fraction of the longest.
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
    prefill_buckets: Tuple[int, ...] = ()

    @property
    def prefill_lengths(self) -> Tuple[int, ...]:
        """Every padded prompt length the prefill compiles for,
        ascending; the last is ``max_prompt_len``."""
        return tuple(sorted({int(b) for b in self.prefill_buckets}
                            | {self.max_prompt_len}))

    def __post_init__(self):
        if any(not 1 <= int(b) <= self.max_prompt_len
               for b in self.prefill_buckets):
            raise ValueError(
                f"prefill_buckets {self.prefill_buckets} must lie in "
                f"[1, max_prompt_len = {self.max_prompt_len}]")
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


def make_decode_step(model, dcfg: DecodeConfig,
                     return_logits: bool = False):
    """Build the jitted one-token-per-sequence decode step.

    Returns ``step(params, pools, tokens, positions, active,
    page_tables, seeds) -> (pools, next_tokens)`` with

    - ``pools``: the carried cache state — the model's named page
      pools (GPT: ``{"k", "v"}``), with its device-side counters under
      ``"counters"`` if it keeps any (DONATED — rebind on every call);
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
    m = served(model)

    def step(params, pools, tokens, positions, active, page_tables, seeds):
        hidden, pools = m.decode(
            params, tokens, positions, active, pools, page_tables,
            attn_impl=dcfg.attn_impl)
        if return_logits:
            logits = jnp.matmul(hidden.astype(jnp.float32),
                                m.head(params).T.astype(jnp.float32))
            return pools, logits
        next_tokens = fused_sample(
            hidden, m.head(params), seeds,
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return pools, next_tokens

    return jax.jit(step, donate_argnums=(1,))


def make_verify_step(model, dcfg: DecodeConfig):
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
    m = served(model)

    def verify(params, pools, tokens, positions, active, page_tables,
               seeds):
        B = tokens.shape[0]
        off = jnp.arange(W, dtype=jnp.int32)
        pos_f = (positions.astype(jnp.int32)[:, None]
                 + off[None, :]).reshape(B * W)
        hidden, pools = m.decode(
            params, tokens.reshape(B * W), pos_f,
            jnp.repeat(active, W), pools, page_tables,
            attn_impl=dcfg.attn_impl, verify_width=W)
        sampled = fused_sample(
            hidden, m.head(params), seeds.reshape(B * W),
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return pools, sampled.reshape(B, W)

    return jax.jit(verify, donate_argnums=(1,))


def _write_prompt(pools, stacks, names, windowed, dcfg: DecodeConfig,
                  page_table_row, prompt_len, start, slot):
    """A prefilled prompt's columns into the pools ``names``, in place:
    ``stacks[name]`` is a paged pool's columns, or, for a ``windowed``
    spec, ``(pooled columns, the open window's own columns)`` (the
    latter go to decode slot ``slot``'s window buffer).  Returns the
    written pools by name."""
    if windowed is not None:
        written = write_prompt_windowed(
            [pools[n] for n in names], [stacks[n][0] for n in names],
            [stacks[n][1] for n in names], page_table_row, prompt_len,
            slot, windowed, dcfg.cache.num_pages, impl=dcfg.attn_impl)
    else:
        written = write_prompt_pools(
            [pools[n] for n in names], [stacks[n] for n in names],
            page_table_row, prompt_len, start=start, impl=dcfg.attn_impl)
    return dict(zip(names, written))


def make_prefill(model, dcfg: DecodeConfig):
    """Build the jitted prompt-prefill step (one compile a padded
    length: ``dcfg.prefill_lengths``).

    Returns ``prefill(params, pools, prompt, prompt_len, start,
    page_table_row, seed, slot) -> (pools, first_token)`` where
    ``prompt`` is (1, S) int32, S one of the padded lengths (zero-padded
    past ``prompt_len``; the padded tail's cache columns go to the
    garbage page and its causal rows are never read), ``start`` is the
    prefix-sharing write window (positions < ``start`` already live in
    shared pool pages and are NOT rewritten; 0 = unshared),
    ``page_table_row`` is the admitted sequence's (P,) table, ``slot``
    its decode slot (read only where the model keeps per-slot state:
    the prompt's final state is installed into that slot's rows, all of
    them, so nothing of the slot's last tenant survives; or a window a
    slot, :class:`~apex_tpu.inference.kv_cache.Windowed`: the model's
    prefill then hands back ``(pooled columns, own columns)`` a pool,
    and the open window's columns go to that slot's buffer) and
    ``first_token`` is sampled from the LAST prompt position's hidden
    state with the same sampling head as decode.  Pools donate, as in
    the decode step.
    """
    m = served(model)
    per_slot = per_slot_names(m.cache_spec())
    windowed = windowed_entry(m.cache_spec(), dcfg.cache)

    def prefill(params, pools, prompt, prompt_len, start, page_table_row,
                seed, slot=None):
        S = prompt.shape[1]
        hidden, stacks = m.prefill(params, prompt, prompt_len,
                                   dcfg.attn_impl)
        names = sorted(n for n in stacks if n not in per_slot)
        new = _write_prompt(pools, stacks, names, windowed, dcfg,
                            page_table_row, prompt_len, start, slot)
        for n in per_slot:
            new[n] = install_rows(pools[n], stacks[n], slot,
                                  impl=dcfg.attn_impl)
        h_last = hidden[jnp.clip(prompt_len - 1, 0, S - 1), 0]  # (H,)
        first = fused_sample(
            h_last[None], m.head(params), seed[None],
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return dict(pools, **new), first[0]

    return jax.jit(prefill, donate_argnums=(1,))


def make_prefill_chunk(model, dcfg: DecodeConfig):
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
    m = served(model)

    def chunk(params, pools, tokens, start_pos, valid, write_start,
              page_table_row):
        off = jnp.arange(C, dtype=jnp.int32)
        pos = start_pos.astype(jnp.int32) + off
        act = off < valid
        wmask = act & (pos >= write_start)
        hidden, pools = m.decode(
            params, tokens, pos, act, pools, page_table_row[None],
            attn_impl=dcfg.attn_impl, verify_width=C, write_mask=wmask)
        h_last = hidden[jnp.clip(valid - 1, 0, C - 1)]
        return pools, h_last

    return jax.jit(chunk, donate_argnums=(1,))


def make_sample_head(model, dcfg: DecodeConfig):
    """The standalone jitted sampling head — hidden (H,) + seed →
    token — used once per chunked admission (the final chunk returns
    ``h_last``; sampling stays OUT of the chunk step so intermediate
    chunks never pay the vocab matmul)."""
    m = served(model)

    def head(params, hidden, seed):
        tok = fused_sample(
            hidden[None], m.head(params), seed[None],
            temperature=dcfg.temperature, top_k=dcfg.top_k,
            impl=dcfg.sample_impl, dot_dtype=dcfg.sample_dot_dtype)
        return tok[0]

    return jax.jit(head)


def decode_logits_tokenwise(params, model, dcfg: DecodeConfig,
                            tokens, prefix: int, page_table_row):
    """The decode↔full-forward parity probe: prefill ``tokens[:,
    :prefix]`` through the model's full forward, then decode positions
    ``prefix..S-1`` one token at a time through the jitted decode step
    (``return_logits=True``, ``dcfg``'s attention impl and cache dtype).

    ``tokens`` is (1, S); the sequence rides slot 0 of the
    ``dcfg.max_batch`` slots, the rest stay inactive.  Returns the
    (S - prefix, V) fp32 logits that the model's full forward gives at
    positions ``prefix..S-1`` (GPT: ``gpt_forward(params, tokens,
    config)[prefix:, 0]``) — to reduction-reorder ulps in fp32, to the
    storage dtype's rounding with a bf16 cache (tests/test_inference.py;
    ``chip_smoke.py`` runs it on the compiled kernels)."""
    m = served(model)
    S = tokens.shape[1]
    B = dcfg.max_batch
    spec = m.cache_spec()
    per_slot = per_slot_names(spec)
    # the prompt padded to S: a paged pool's first ``prefix`` columns do
    # not see the tail (causal), a per-slot state is taken AT ``prefix``
    head = jnp.where(jnp.arange(S)[None] < prefix, tokens, 0)
    _, stacks = jax.jit(
        lambda p, t: m.prefill(p, t, jnp.int32(prefix), dcfg.attn_impl))(
            params, head)
    names = sorted(n for n in stacks if n not in per_slot)
    pools = alloc_named_pools(spec, dcfg.cache, slots=B)
    windowed = windowed_entry(spec, dcfg.cache)
    if windowed is None:
        stacks = dict(stacks, **{n: stacks[n][:, :prefix] for n in names})
    pools.update(_write_prompt(       # a windowed S is whole windows
        pools, stacks, names, windowed, dcfg, page_table_row,
        jnp.int32(prefix), 0, jnp.int32(0)))
    for n in per_slot:
        pools[n] = install_rows(pools[n], stacks[n], jnp.int32(0),
                                impl=dcfg.attn_impl)
    step = make_decode_step(m, dcfg, return_logits=True)
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


# ------------------------------------------------- generation by blocks
#: the kind of a block-forward.  Column ``W`` of the block step's
#: readback says what the slot's OPEN half did: nothing (an inactive
#: slot, or one past its last block) or a denoising pass; a commit rides
#: the step's held half and has a column of its own (``W + 2``), and the
#: scheduler writes it into a request's trace as a ``BLOCK_COMMIT`` row
BLOCK_IDLE, BLOCK_DENOISE, BLOCK_COMMIT = -1, 0, 1


def unmask_counts(block_length: int, denoising_steps: int):
    """``n_t``, the masked positions denoising pass ``t`` of a block
    unmasks (at least; a pass never unmasks more than are masked):
    ``block_length // T + [t < block_length mod T]``."""
    W, T = int(block_length), int(denoising_steps)
    return [W // T + (t < W % T) for t in range(T)]


def block_passes(block_length: int, denoising_steps: int, masked: int):
    """Forwards a block of ``masked`` masked positions takes under the
    static schedule, which is also the rows it leaves in a request's
    ``block_trace``: the denoising passes until none is left, and the
    commit.  Every commit but a request's last rides the next block's
    first denoising pass (:func:`make_block_step`), so a request of
    ``n`` blocks holds its slot for the sum of these less ``n - 1``
    steps."""
    left, passes = int(masked), 0
    for n in unmask_counts(block_length, denoising_steps):
        if left <= 0:
            break
        left -= n
        passes += 1
    return passes + 1


def init_block_state(max_batch: int, block_length: int, mask_id: int):
    """The block step's carried per-slot state, on the device: ``ids``
    (B, W) the OPEN block's current ids (``mask_id`` where a position is
    still to be generated), ``passes`` (B,) the denoising passes it has
    had, ``pos`` (B,) its first position; and the HELD block: ``held``
    (B, W) the ids of the clean block before it, at ``pos - W``, and
    ``held_live`` (B,) bool, whether its keys and values are still to
    be stored (set by the pass that left the block clean, cleared by the
    next pass of the slot, which commits it, and by an admission into
    the slot)."""
    return {"ids": jnp.full((max_batch, block_length), mask_id, jnp.int32),
            "passes": jnp.zeros((max_batch,), jnp.int32),
            "pos": jnp.zeros((max_batch,), jnp.int32),
            "held": jnp.zeros((max_batch, block_length), jnp.int32),
            "held_live": jnp.zeros((max_batch,), bool)}


def _chosen(masked, conf, n_t, remasking: str, threshold: float):
    """The masked positions a denoising pass unmasks, (B, W) bool.
    ``low_confidence_static``: the ``n_t`` of highest confidence (ties:
    the lowest index); ``low_confidence_dynamic``: every one whose
    confidence passes ``threshold``, or the ``n_t`` best where fewer
    pass; ``sequential``: the leftmost ``n_t``."""
    if remasking == "sequential":
        rank = jnp.cumsum(masked, axis=1, dtype=jnp.int32) - 1
        return masked & (rank < n_t[:, None])
    c = jnp.where(masked, conf, -jnp.inf)
    index = jnp.arange(c.shape[1], dtype=jnp.int32)
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None])
        & (index[None, None, :] < index[None, :, None]))
    best = masked & (jnp.sum(ahead, axis=2, dtype=jnp.int32) < n_t[:, None])
    if remasking == "low_confidence_static":
        return best
    over = masked & (conf > threshold)
    enough = jnp.sum(over, axis=1, dtype=jnp.int32) >= n_t
    return jnp.where(enough[:, None], over, best)


def make_block_step(model, dcfg: DecodeConfig, return_logits: bool = False):
    """Build the jitted BLOCK step of a model that generates by
    diffusion over blocks (it declares ``block_length`` W, ``mask_id``,
    ``remasking`` and ``confidence_threshold``, and brings
    ``decode_block``).

    Returns ``step(params, pools, blocks, steps, ends, active,
    page_tables, seeds) -> (pools, blocks, out)`` with ``pools`` as in
    :func:`make_decode_step` and

    - ``blocks``: the carried per-slot block state
      (:func:`init_block_state`; DONATED, like the pools).  It lives on
      the device so that the host can launch step n+1 before it has
      read step n: what a pass unmasked, and whether a block is done,
      are the device's to know;
    - ``steps`` (B,) int32: each slot's ``denoising_steps`` T (1..W);
      ``ends`` (B,): the position a slot's sequence ends at, rounded up
      to a block: a slot whose open block starts there has no open
      block, whatever ``active`` says;
    - ``active`` (B,) bool, ``page_tables`` (B, P), ``seeds`` (B,)
      uint32 (a slot's draw; row ``w`` of its block draws from ``seed +
      w``).

    A step forwards ``2W`` rows a slot, two halves that are live or
    dead each on its own.  **The open half** (live iff ``active & pos <
    ends``) is a DENOISING pass: the open block's W ids, a mask among
    them, are forwarded at ``pos .. pos + W - 1`` against the cached
    columns before them and their own (the pass's keys and values are
    written over the block's columns in place); the fused head gives
    every row its token and that token's confidence
    (:func:`~apex_tpu.ops.decode_sampling_pallas.fused_sample_confidence`;
    no (rows, V) logits in HBM; the mask's own row is left out of the
    draw and of the softmax, so a position is never unmasked into a
    mask), and :func:`_chosen` positions take their tokens.  A pass that
    leaves the block without a mask moves the slot on AT ONCE: the clean
    block becomes the HELD block, the open block all masks at ``pos +
    W``.  **The held half** (live iff ``active & held_live``) is the
    held block's COMMIT: its clean ids forwarded at ``pos - W .. pos -
    1`` so that the keys and values that stay in the cache are those of
    the clean tokens, in the same forward as the open block's first
    denoising pass, which sees them (``decode_block``: both blocks'
    columns are written before any row attends).  A commit needs no
    logits: only the open rows go to the head.  So a block costs its
    slot T steps, not T + 1; a request's last block has no block after
    it (``pos == ends``: the open half dead) and commits in a step of
    its own.

    ``out`` (B, 2W + 3) int32 is what the host reads back: the open
    block's ids after the pass, the pass's kind (``BLOCK_DENOISE``, or
    ``BLOCK_IDLE`` where the open half was dead), how many positions it
    unmasked; then whether a commit rode the step (0/1) and the ids it
    committed.

    With ``return_logits=True`` the step returns ``(pools, blocks,
    logits (B, W, V))`` float32, the open rows', and leaves the block
    state as it was: the parity probe."""
    from apex_tpu.inference.kv_cache import COUNTERS

    m = served(model)
    W, mask_id = int(m.block_length), int(m.mask_id)
    if dcfg.top_k:
        raise NotImplementedError(
            "the block step's head gives a token and its confidence over "
            "the whole vocabulary: top_k is not built for it")
    names = tuple(m.counter_names)
    own = [names.index(n) for n in ("blk_denoise_passes",
                                    "blk_commit_passes",
                                    "blk_tokens_unmasked",
                                    "blk_commits_fused")]

    def step(params, pools, blocks, steps, ends, active, page_tables, seeds):
        ids, passes, pos = blocks["ids"], blocks["passes"], blocks["pos"]
        held = blocks["held"]
        B = ids.shape[0]
        denoise = active & (pos < ends)
        commit = active & blocks["held_live"]
        hidden, pools = m.decode_block(
            params, jnp.concatenate([held, ids], axis=1).reshape(2 * B * W),
            pos, jnp.stack([commit, denoise], axis=1), pools, page_tables,
            dcfg.attn_impl)
        if return_logits:
            logits = jnp.matmul(hidden.astype(jnp.float32),
                                m.head(params).T.astype(jnp.float32))
            return pools, blocks, logits.reshape(B, W, -1)
        row_seeds = (seeds.astype(jnp.uint32)[:, None]
                     + jnp.arange(W, dtype=jnp.uint32)[None]).reshape(B * W)
        x0, conf = fused_sample_confidence(
            hidden, m.head(params), row_seeds,
            temperature=dcfg.temperature, exclude=mask_id,
            impl=dcfg.sample_impl,
            dot_dtype=dcfg.sample_dot_dtype)
        masked = ids == mask_id
        T = jnp.clip(steps.astype(jnp.int32), 1, W)
        n_t = W // T + (passes < W % T).astype(jnp.int32)
        chosen = _chosen(masked, conf.reshape(B, W), n_t, m.remasking,
                         float(m.confidence_threshold)) & denoise[:, None]
        after = jnp.where(chosen, x0.reshape(B, W), ids)
        unmasked = jnp.sum(chosen, axis=1, dtype=jnp.int32)
        clean = denoise & ~jnp.any(after == mask_id, axis=1)
        kind = jnp.where(denoise, BLOCK_DENOISE, BLOCK_IDLE)
        out = jnp.concatenate(
            [after, kind[:, None].astype(jnp.int32), unmasked[:, None],
             commit[:, None].astype(jnp.int32), held], axis=1)
        blocks = {
            "ids": jnp.where(clean[:, None], mask_id, after),
            "passes": jnp.where(clean, 0, passes + denoise),
            "pos": pos + W * clean.astype(jnp.int32),
            "held": jnp.where(clean[:, None], after, held),
            # committed by this pass, unless the pass left a new one
            "held_live": clean | (blocks["held_live"] & ~active)}
        if COUNTERS in pools:
            add = jnp.zeros((len(names),), jnp.int32).at[jnp.asarray(own)] \
                .set(jnp.stack([jnp.sum(denoise, dtype=jnp.int32),
                                jnp.sum(commit, dtype=jnp.int32),
                                jnp.sum(unmasked),
                                jnp.sum(commit & denoise, dtype=jnp.int32)]))
            pools = dict(pools, **{COUNTERS: pools[COUNTERS] + add})
        return pools, blocks, out

    return jax.jit(step, donate_argnums=(1,) if return_logits else (1, 2))


def make_block_prefill(model, dcfg: DecodeConfig):
    """Build the jitted prompt prefill of a block-generating model (one
    compile a padded length, as :func:`make_prefill`).

    Returns ``prefill(params, pools, prompt, keep, page_table_row) ->
    pools``: ``prompt`` (1, S) runs through the model's full forward
    under its block-causal mask and the first ``keep`` positions' cache
    columns, the WHOLE BLOCKS of the prompt, are written into the
    sequence's pages.  It samples nothing and reads nothing back: what
    is left of the prompt past ``keep`` opens the first block beside
    masks, and the first tokens come from that block's commit."""
    m = served(model)

    def prefill(params, pools, prompt, keep, page_table_row):
        _, stacks = m.prefill(params, prompt, keep, dcfg.attn_impl)
        names = sorted(stacks)
        new = _write_prompt(pools, stacks, names, None, dcfg,
                            page_table_row, keep, 0, None)
        return dict(pools, **new)

    return jax.jit(prefill, donate_argnums=(1,))
