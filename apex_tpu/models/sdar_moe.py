"""SDAR-MoE: a sparse-expert decoder that GENERATES BY DIFFUSION OVER
BLOCKS, served.

The sixth served family (docs/inference.md), and a file of its own: it
shares :mod:`apex_tpu.ops`, :mod:`apex_tpu.inference` and the held-expert
layer (:func:`apex_tpu.transformer.expert_parallel.held_experts_ffn`)
with the other families and no block code.  What is different from all
of them:

- **a step forwards a BLOCK of ``block_length`` positions a slot**, some
  of them the mask token, and yields no token, some tokens or a whole
  block (:func:`apex_tpu.inference.decode.make_block_step` has the
  procedure; this file has the forward it runs), and beside it the
  block BEFORE it, clean, whose commit rides the same forward;
- **visibility is causal by block, not by token**: key ``j`` is visible
  to query ``i`` iff ``j // W <= i // W``.  Inside a block attention is
  bidirectional, so a block's ``W`` rows see the SAME columns, the
  cached ones and their own (``block_decode_attention``: a slot's two
  blocks ride one walk of its live pages a layer, a length a block),
  and the prompt is prefilled under the same mask
  (``block_causal_attention``);
- **every pass rewrites its block's keys and values in place**
  (``write_block_pools``): a denoising pass's are overwritten by the
  next pass's and at last by the COMMIT's, those of the clean tokens,
  which is what later blocks attend to.  The commit is no pass of its
  own: the clean block is HELD and forwarded beside the block that
  opens after it, whose rows see its clean columns (written in that
  layer before any row attends);
- **the logits at position ``i`` predict the token AT ``i``** (a mask
  predicts itself; no shift by one);
- **a softmax router**: ``p = softmax(x Wr)`` in float32 over all
  experts, the ``num_experts_per_tok`` largest chosen, their weights
  renormalised over the chosen (``norm_topk_prob``); no bias, no groups,
  no shared expert.  This process holds the experts ``held`` of the
  router's ``num_experts`` and computes their part of the result.

The layer (``h`` the stream, every norm an RMSNorm with a gain)::

    x = norm(h; attn_norm)
    q, k, v = x Wq, x Wk, x Wv      heads of head_dim, 8 query heads a kv head
    q, k = norm(q; q_norm), norm(k; k_norm)     a head, over its channels
    A = (softmax(rope(q) rope(k)^T / sqrt(d), causal by block) v) Wo
    h = h + A
    x2 = norm(h; ffn_norm)
    h = h + sum_{e chosen and held} w_e (silu(x2 Wg_e) * (x2 Wu_e)) Wd_e

and ``logits = norm(h; final_norm) W_head^T``.  Norm gains and the
router are float32; matrices, activations and cached keys and values the
compute dtype.  The layers are ONE ``lax.scan`` with the pools as carry;
the held experts' weights are not sliced by the scan (the grouped matmul
takes the stack and the layer's index).  Tensor-parallel and training
variants do not exist.
"""

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from apex_tpu.ops.rope import apply_rope, apply_rope_at
from apex_tpu.transformer.expert_parallel import (
    expert_buffer_rows, held_experts_ffn,
)

__all__ = ["COUNTER_NAMES", "EXPERT_LEAVES", "FLOAT32_LEAVES", "REMASKING",
           "SDARMoEConfig", "SDARMoEServed", "forward", "forward_block",
           "init_params", "param_shapes"]

#: the device-side counters, in the order of the carried vector: live
#: rows forwarded by block steps (``block_length`` a live half of a
#: slot), block-forwards that were denoising passes and commits (a step
#: of a slot may be one of each), tokens unmasked (those three are the
#: step's: ``make_block_step``), the columns a slot's one walk had to
#: read summed over live slots (the longer of its two lengths; every
#: layer reads as many), the commits that rode a denoising pass of their
#: slot (the step's too), and the expert layer's three, summed over the
#: layers, as the latent family keeps them
COUNTER_NAMES = ("blk_rows_forwarded", "blk_denoise_passes",
                 "blk_commit_passes", "blk_tokens_unmasked", "blk_kv_cols",
                 "blk_commits_fused",
                 "moe_assignments_held", "moe_assignments_all",
                 "moe_experts_hit")
#: leaves kept in float32 whatever ``param_dtype``
FLOAT32_LEAVES = ("attn_norm", "ffn_norm", "q_norm", "k_norm", "final_norm",
                  "router")
#: the held experts' weights: the layer scan leaves them STACKED
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
#: how a denoising pass picks the masked positions it unmasks
REMASKING = ("low_confidence_static", "low_confidence_dynamic",
             "sequential")


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    """Shapes and constants under the published config's names, and the
    generation procedure's (``block_length`` onwards: the released
    ``generate.py``'s arguments)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    #: the router's width: ALL the experts of a layer
    num_experts: int = 128
    num_experts_per_tok: int = 8
    #: the experts this process holds: ``held_count`` ids from
    #: ``held_start`` (None: all of them)
    held_start: int = 0
    held_count: Any = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    block_length: int = 4
    #: the id a position still to be generated holds; None: the last row
    #: of the vocabulary held
    mask_token_id: Any = None
    #: denoising passes a block, where a request does not say
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        W = self.block_length
        if W < 1 or W & (W - 1):
            raise ValueError(f"block_length {W} must be a power of two")
        if not 1 <= self.denoising_steps <= W:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must lie in "
                f"[1, block_length = {W}]")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r} is none of "
                             f"{REMASKING}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into kv heads")
        if not 0 <= self.held.start < self.held.stop <= self.num_experts:
            raise ValueError(f"held {self.held} is no part of the "
                             f"{self.num_experts} experts")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_id} is no row of "
                             f"the {self.vocab_size} held")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "SDARMoEConfig":
        """From a published ``config.json`` dict (``model_type:
        sdar_moe``).  The keys that pick the mechanism are held to what
        this file implements: every layer sparse, no bias, SiLU, an
        untied head, no rotary scaling, no sliding window, the chosen
        experts' weights renormalised.  All the experts the config
        counts are held; one chip's share of a wider router is the
        caller's to say, by overriding ``num_experts`` (the router's
        width) together with ``held_start``/``held_count``.  Any field
        may be overridden."""
        want = {"model_type": "sdar_moe", "attention_bias": False,
                "decoder_sparse_step": 1, "mlp_only_layers": [],
                "hidden_act": "silu", "norm_topk_prob": True,
                "rope_scaling": None, "use_sliding_window": False,
                "tie_word_embeddings": False}
        for key, value in want.items():
            if conf.get(key, value) != value:
                raise ValueError(
                    f"config {key} = {conf[key]!r}: this file serves "
                    f"{key} = {value!r}")
        heads = conf["num_attention_heads"]
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            num_hidden_layers=conf["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=conf.get("num_key_value_heads") or heads,
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            moe_intermediate_size=conf["moe_intermediate_size"],
            num_experts=conf["num_experts"],
            num_experts_per_tok=conf["num_experts_per_tok"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=float(conf["rope_theta"]),
            max_position_embeddings=conf["max_position_embeddings"])
        kw.update(overrides)
        return cls(**kw)

    @property
    def held(self) -> range:
        n = self.num_experts - self.held_start if self.held_count is None \
            else self.held_count
        return range(self.held_start, self.held_start + n)

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1 if self.mask_token_id is None \
            else int(self.mask_token_id)

    def served_model(self) -> "SDARMoEServed":
        return SDARMoEServed(self)


# ------------------------------------------------------------- parameters
def param_shapes(c: SDARMoEConfig) -> Dict:
    """The parameter tree's shapes: the layers stacked on a leading
    axis, matrices input-major, ``wqkv`` the three attention projections
    side by side (queries, keys, values), the held experts' ``we_*``
    ``(layers, held, ...)`` in id order."""
    L, H, F, d = (c.num_hidden_layers, c.hidden_size,
                  c.moe_intermediate_size, c.head_dim)
    nq, nkv, n = c.num_attention_heads * d, c.num_key_value_heads * d, \
        len(c.held)
    return {
        "embed": (c.vocab_size, H), "head": (c.vocab_size, H),
        "final_norm": (H,),
        "layers": {
            "attn_norm": (L, H), "ffn_norm": (L, H),
            "q_norm": (L, d), "k_norm": (L, d),
            "wqkv": (L, H, nq + 2 * nkv), "wo": (L, nq, H),
            "router": (L, H, c.num_experts),
            "we_gate": (L, n, H, F), "we_up": (L, n, H, F),
            "we_down": (L, n, F, H)},
    }


def init_params(config: SDARMoEConfig, key) -> Dict:
    """Seeded parameters: a matrix ``N(0, 1 / fan_in)`` (unit variance
    in, unit variance out, so that attention and experts each move the
    stream), the embedding ``N(0, 1)``, the router ``N(0, 1 / fan_in)``
    (logits of unit variance: the choice is spread), gains ``1 + N(0,
    0.02)``.  :data:`FLOAT32_LEAVES` float32, all else
    ``param_dtype``."""
    c = config
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith("norm"):
            x = 1.0 + 0.02 * x
        elif name != "embed":
            fan_in = shape[-1] if name == "head" else shape[-2]
            x = x * fan_in ** -0.5
        out.append(x if name in FLOAT32_LEAVES else x.astype(c.param_dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ pieces
def _rms_norm(x, gain, eps):
    """RMSNorm in float32 over the last axis; the result in ``x``'s
    dtype."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * gain.astype(jnp.float32)).astype(x.dtype)


def _embed(params, tokens, c: SDARMoEConfig):
    return jnp.take(params["embed"], tokens, axis=0).astype(c.compute_dtype)


def _qkv(x, p, c: SDARMoEConfig):
    """(T, H) normed rows -> q (T, heads, d), k, v (T, kv heads, d),
    unrotated; q and k normed a head."""
    d = c.head_dim
    y = jnp.matmul(x, p["wqkv"].astype(c.compute_dtype))
    nq, nk = c.num_attention_heads * d, c.num_key_value_heads * d
    q = _rms_norm(y[:, :nq].reshape(-1, c.num_attention_heads, d),
                  p["q_norm"], c.rms_norm_eps)
    k = _rms_norm(y[:, nq:nq + nk].reshape(-1, c.num_key_value_heads, d),
                  p["k_norm"], c.rms_norm_eps)
    return q, k, y[:, nq + nk:].reshape(-1, c.num_key_value_heads, d)


def _rest(h, attn, p, experts, index, c: SDARMoEConfig, token_mask, impl,
          compact=False):
    """The block after its attention: ``attn`` (T, heads, d) through
    ``wo`` into the stream, then the held experts' routed part.
    ``experts``: the STACKED expert leaves, ``index`` this layer's place
    in them.  ``compact``: the held share of the rows' assignments
    compacted and walked in chunks (``held_experts_ffn(buffer_rows=)``),
    so that the layer's glue costs what the held assignments cost (an
    eighth of them at 16 experts of 128), not what all ``top_k`` a row
    would: the block step's form, 48 times a step.  The prefill keeps
    the whole buffer: a second form in its four programs costs every
    start their tracing (0.9 s of a 12 s set-up: my chip runs, PR 45)
    for a pass that runs once a request.  Returns ``(h, counts)``."""
    cd = c.compute_dtype
    h = h + jnp.matmul(attn.reshape(attn.shape[0], -1).astype(cd),
                       p["wo"].astype(cd))
    x = _rms_norm(h, p["ffn_norm"], c.rms_norm_eps)
    A = x.shape[0] * c.num_experts_per_tok
    routed, counts = held_experts_ffn(
        x, dict(experts, router=p["router"]), c.held,
        top_k=c.num_experts_per_tok, n_group=1, topk_group=1, scale=1.0,
        token_mask=token_mask, layer=index, softmax=True,
        impl={"auto": "auto", "pallas": "pallas"}.get(impl, "xla"),
        buffer_rows=expert_buffer_rows(
            x.shape[0], c.num_experts_per_tok, len(c.held), c.num_experts,
            multiple=min(512, A)) if compact else None)
    return h + routed, counts


def _layers(params):
    """``(scanned leaves, the stacked expert leaves)`` of the tree."""
    layers = params["layers"]
    experts = {k: layers[k] for k in EXPERT_LEAVES}
    return {k: v for k, v in layers.items() if k not in experts}, experts


def _count(counts):
    return jnp.stack([counts["assignments_held"], counts["assignments_all"],
                      counts["experts_hit"]]).astype(jnp.int32)


def forward(params, tokens, config: SDARMoEConfig, attn_impl: str = "auto",
            return_hidden: bool = False, return_cache: bool = False,
            token_mask=None):
    """Full forward of (B, S) ``tokens`` under the block-causal mask.
    Returns float32 logits (B, S, V): row ``i`` predicts the token AT
    ``i``; or with ``return_hidden`` the final-normed activations the
    head multiplies (B, S, H); with ``return_cache`` also ``{"k", "v"}``
    (L, B, S, kv heads, d), the rotated keys and the values.
    ``token_mask`` (B, S): positions outside it route to no expert
    (padding; their rows are never read)."""
    from apex_tpu.ops.attention import block_causal_attention

    c = config
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    rest, experts = _layers(params)
    mask = None if token_mask is None else token_mask.reshape(B * S)

    def layer(h, inp):
        p, index = inp
        x = _rms_norm(h, p["attn_norm"], c.rms_norm_eps)
        q, k, v = _qkv(x, p, c)
        bhsd = lambda t: t.reshape((B, S) + t.shape[1:]).transpose(0, 2, 1, 3)
        q, k = apply_rope(bhsd(q), positions, c.rope_theta), \
            apply_rope(bhsd(k), positions, c.rope_theta)
        attn = block_causal_attention(q, k, bhsd(v), c.block_length,
                                      impl=attn_impl)
        attn = attn.transpose(0, 2, 1, 3).reshape(B * S, -1, c.head_dim)
        h, _ = _rest(h, attn, p, experts, index, c, mask, attn_impl)
        kept = (k.transpose(0, 2, 1, 3),
                v.reshape(B, S, -1, c.head_dim)) if return_cache else None
        return h, kept

    h, kept = jax.lax.scan(
        layer, _embed(params, tokens.reshape(B * S), c),
        (rest, jnp.arange(c.num_hidden_layers, dtype=jnp.int32)))
    out = _rms_norm(h, params["final_norm"], c.rms_norm_eps) \
        .reshape(B, S, -1)
    if not return_hidden:
        out = jnp.matmul(out.astype(jnp.float32),
                         params["head"].T.astype(jnp.float32))
    if not return_cache:
        return out
    return out, dict(zip(("k", "v"), kept))


def forward_block(params, tokens, positions, active, pools, page_tables,
                  config: SDARMoEConfig, attn_impl: str = "auto"):
    """TWO BLOCKS a slot over the paged cache, the HELD block and the
    OPEN one after it: ``tokens`` (B * 2W,) the slots' rows side by
    side, a slot's ``W`` held ids (a clean block whose keys and values
    are still to be stored) then its ``W`` open ids (masks among them);
    ``positions`` (B,) each slot's OPEN block's start (a multiple of
    ``W``; the held block sits at ``positions - W``); ``active`` (B, 2)
    bool: whether the held half and the open half are live.

    ``pools``: ``"k"`` and ``"v"``, (layers, pages, kv heads, d,
    page_size), and optionally ``"counters"``.  A layer first writes
    both blocks' keys and values into their page or two pages
    (``apex_kv_write``: the open block's over whatever an earlier pass
    left there, the held block's for good), then all ``2W`` rows attend
    in one walk of the slot's pages (``apex_decode_attention``, the rows
    folded into the group): the held rows over ``position`` columns, the
    cached blocks and their own, the open rows over ``position + W``,
    those and the held block's CLEAN columns written in this very
    layer, and their own.  So the held block's columns are what a
    forward of its clean tokens alone would have stored, and the open
    block sees them as if that forward had run a step before.  Returns
    ``(hidden (B * W, H), pools)``: the OPEN rows, final-normed (a held
    row needs no logits)."""
    from apex_tpu.inference.kv_cache import COUNTERS, write_block_pools
    from apex_tpu.ops.decode_attention_pallas import block_decode_attention

    c = config
    W = c.block_length
    B = positions.shape[0]
    positions = positions.astype(jnp.int32)
    start = positions - W                   # the held block's
    rows = jnp.maximum(start[:, None]
                       + jnp.arange(2 * W, dtype=jnp.int32)[None], 0) \
        .reshape(-1)
    lengths = jnp.where(active, jnp.stack([positions, positions + W], 1),
                        0).astype(jnp.int32)
    live_rows = jnp.repeat(active, W, axis=1).reshape(-1)
    rest, experts = _layers(params)

    def body(carry, inp):
        h, k_pool, v_pool, counted = carry
        p, index = inp
        x = _rms_norm(h, p["attn_norm"], c.rms_norm_eps)
        q, k, v = _qkv(x, p, c)
        q = apply_rope_at(q, rows, c.rope_theta)
        k = apply_rope_at(k, rows, c.rope_theta)
        k_pool, v_pool = write_block_pools(
            (k_pool, v_pool), (k, v), page_tables, start, active, W,
            layer=index, impl=attn_impl)
        attn = block_decode_attention(q, k_pool, v_pool, page_tables,
                                      lengths, 2 * W, impl=attn_impl,
                                      layer=index)
        h, counts = _rest(h, attn, p, experts, index, c, live_rows,
                          attn_impl, compact=True)
        return (h, k_pool, v_pool, counted + _count(counts)), None

    (h, k_pool, v_pool, counted), _ = jax.lax.scan(
        body, (_embed(params, tokens, c), pools["k"], pools["v"],
               jnp.zeros((3,), jnp.int32)),
        (rest, jnp.arange(c.num_hidden_layers, dtype=jnp.int32)))
    out = dict(pools, k=k_pool, v=v_pool)
    if COUNTERS in pools:
        # a slot's ONE walk reads its longer length, once a step
        add = jnp.zeros((len(COUNTER_NAMES),), jnp.int32) \
            .at[COUNTER_NAMES.index("blk_rows_forwarded")] \
            .set(W * jnp.sum(active, dtype=jnp.int32)) \
            .at[COUNTER_NAMES.index("blk_kv_cols")] \
            .set(jnp.sum(jnp.max(lengths, axis=1))) \
            .at[COUNTER_NAMES.index("moe_assignments_held"):].set(counted)
        out[COUNTERS] = pools[COUNTERS] + add
    open_rows = h.reshape(B, 2, W, -1)[:, 1].reshape(B * W, -1)
    return _rms_norm(open_rows, params["final_norm"], c.rms_norm_eps), out


# ----------------------------------------------------------- served model
class SDARMoEServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md), the block step's part
    included: ``block_length`` says that the model generates by blocks,
    and the scheduler then runs ``decode_block`` where another family's
    ``decode`` would run."""

    #: a block a slot a step, not consecutive positions under their own
    #: causal lengths: no speculative verify, no chunks
    multi_position = False
    #: rotary positions: no learned table bounds a request
    max_positions = None
    #: the leaves every served program reads only as
    #: ``leaf.astype(compute_dtype)``.  Not :data:`FLOAT32_LEAVES`, not
    #: the held experts' ``we_*`` (the grouped matmul reads them as they
    #: are stored), not ``embed``/``head``
    cast_once_leaves = ("wqkv", "wo")
    counter_names = COUNTER_NAMES

    def __init__(self, config: SDARMoEConfig):
        self.config = config
        # what a block-generating model declares
        self.block_length, self.mask_id = config.block_length, config.mask_id
        self.denoising_steps = config.denoising_steps
        self.remasking = config.remasking
        self.confidence_threshold = config.confidence_threshold

    def cache_spec(self) -> Dict[str, tuple]:
        c = self.config
        kv = (c.num_hidden_layers, c.num_key_value_heads, c.head_dim)
        return {"k": kv, "v": kv}

    def head(self, params):
        return params["head"]

    def serving_params(self, params):
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt -> final-normed hidden (S, 1, H) and the
        paged pools' columns (L, S, kv heads, d).  ``prompt_len``: the
        positions to keep, whole blocks of the prompt (the scheduler
        rounds down: what is left over opens the first block)."""
        S = prompt.shape[1]
        hidden, cache = forward(
            params, prompt, self.config, attn_impl=attn_impl,
            return_hidden=True, return_cache=True,
            token_mask=jnp.arange(S, dtype=jnp.int32)[None] < prompt_len)
        return hidden.transpose(1, 0, 2), {n: x[:, 0]
                                           for n, x in cache.items()}

    def decode_block(self, params, tokens, positions, active, pools,
                     page_tables, attn_impl):
        return forward_block(params, tokens, positions, active, pools,
                             page_tables, self.config, attn_impl=attn_impl)

    def decode(self, *args, **kwargs):
        raise NotImplementedError(
            "a block-generating model has no one-token decode forward: the "
            "scheduler runs decode_block (make_block_step)")
