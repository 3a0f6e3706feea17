"""EvaByte: a byte-level decoder with EVA attention, served.

The third served family (docs/inference.md), and a file of its own: it
shares :mod:`apex_tpu.ops` and :mod:`apex_tpu.inference` with the other
two and no block code, so nothing on the latent family's path
(``mla_moe._block``, its scan over segments) gains a branch for it.
What is different from both:

- **the residual stream is float32** (``fp32_skip_add``) and every norm
  is an RMSNorm whose gain is ``1 + g`` (``norm_add_unit_offset``);
- **attention is EVA's** (:mod:`apex_tpu.ops.eva`): full-head rotary
  queries and keys (``rope_theta`` 100000), an exact window of
  ``window_size`` positions and one pooled key/value a ``chunk_size``
  positions of every earlier window, pooled by the learned ``phi`` and
  ``mu`` of each head, in ONE softmax;
- **the cache is windowed** (:class:`apex_tpu.inference.kv_cache
  .Windowed`): a pool of pooled columns, a page a window, and a window
  buffer a decode slot that starts again from empty every
  ``window_size`` positions;
- **the head has ``num_pred_heads`` x ``vocab_size`` rows**, head-major:
  head ``i`` predicts byte ``t + 1 + i``.  The server samples from the
  first ``vocab_size`` rows (next-byte generation; self-drafting from
  the other heads needs a multi-position decode over this cache:
  ROADMAP, Queue 2); :func:`forward` gives all of them.

The layer, for a query at ``t`` in window ``w = t // window_size``::

    x = rmsnorm(h) * (1 + g);  q, k, v = x Wq, x Wk, x Wv;  rotary(q, k)
    a_s = q . k_s / sqrt(d)         s in window w, s <= t
    b_c = q . ktilde_c / sqrt(d)    c a chunk of windows 0 .. w - 1
    p = softmax([a ; b]);  o = sum p_s v_s + sum p_c vtilde_c
    h += o Wo;   h += (silu(x' Wg) * (x' Wu)) Wd,  x' = rmsnorm(h)(1 + g')

A chunk's pooled pair is visible only once its whole window has closed
(inside its own window a query sees the tokens themselves); a
sequence's last, partial chunk has none.
"""

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from apex_tpu.ops.rope import apply_rope, apply_rope_at

__all__ = ["COUNTER_NAMES", "EvaByteConfig", "EvaByteServed", "forward",
           "forward_decode", "init_params", "param_shapes"]

#: device-side counters of the decode step, summed over its active
#: slots: the window columns and the pooled columns a layer's attention
#: had to read (the same in every layer: the bytes are these times the
#: layers), the chunks and the windows that closed
COUNTER_NAMES = ("eva_window_cols", "eva_summary_cols", "eva_chunks_closed",
                 "eva_windows_closed")


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """Shapes and constants under the published config's names."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    max_position_embeddings: int = 32768
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size {self.window_size} must hold whole chunks "
                f"of {self.chunk_size}")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "EvaByteConfig":
        """From a published ``config.json`` dict (``model_type:
        evabyte``).  The keys that pick the mechanism are held to what
        this file implements: ``attention_class: eva`` with no
        ``num_chunks`` (the window and the chunk size give the chunks),
        ``norm_add_unit_offset``, ``fp32_skip_add``, ``fp32_logits``,
        one key/value head a query head, no attention bias, SiLU, an
        untied head, no rotary scaling.  Any field may be overridden."""
        want = {"model_type": "evabyte", "attention_class": "eva",
                "num_chunks": None, "norm_add_unit_offset": True,
                "fp32_skip_add": True, "fp32_logits": True,
                "attention_bias": False, "hidden_act": "silu",
                "tie_word_embeddings": False, "rope_scaling": None,
                "num_key_value_heads": conf["num_attention_heads"]}
        for key, value in want.items():
            if conf.get(key, value) != value:
                raise ValueError(
                    f"config {key} = {conf[key]!r}: this file serves "
                    f"{key} = {value!r}")
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            intermediate_size=conf["intermediate_size"],
            num_hidden_layers=conf["num_hidden_layers"],
            num_attention_heads=conf["num_attention_heads"],
            num_pred_heads=conf["num_pred_heads"],
            window_size=conf["window_size"], chunk_size=conf["chunk_size"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=float(conf["rope_theta"]),
            max_position_embeddings=conf["max_position_embeddings"])
        kw.update(overrides)
        return cls(**kw)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def cache_entry(self):
        """The cache entry of both pools, ``k`` and ``v``: a column a
        chunk in the allocator's pages, a window a decode slot."""
        from apex_tpu.inference.kv_cache import Windowed

        return Windowed(self.num_hidden_layers, self.num_attention_heads,
                        self.head_dim, self.chunk_size, self.window_size)

    def served_model(self) -> "EvaByteServed":
        return EvaByteServed(self)


def param_shapes(c: EvaByteConfig) -> Dict:
    """The parameter tree's shapes: the layers stacked on a leading
    axis, matrices input-major, ``wqkv`` the three projections side by
    side."""
    L, H, I = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    heads, d = c.num_attention_heads, c.head_dim
    return {
        "embed": (c.vocab_size, H),
        "head": (c.num_pred_heads * c.vocab_size, H),
        "final_norm": (H,),
        "layers": {
            "attn_norm": (L, H), "ffn_norm": (L, H),
            "wqkv": (L, H, 3 * H), "wo": (L, H, H),
            "phi": (L, heads, d), "mu": (L, heads, d),
            "w_gate": (L, H, I), "w_up": (L, H, I), "w_down": (L, I, H)},
    }


def init_params(config: EvaByteConfig, key, std: float = 0.01275) -> Dict:
    """Seeded parameters: matrices ``N(0, std)`` (the published
    ``init_std``), norm gains ``g ~ N(0, 0.02)`` (the norm multiplies by
    ``1 + g``), ``phi`` and ``mu`` ``N(0, 1)`` clipped to one, times
    ``head_dim ** -0.5``."""
    c = config
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name in ("phi", "mu"):
            x = jnp.clip(jax.random.normal(k, shape), -1, 1) \
                * c.head_dim ** -0.5
        else:
            x = jax.random.normal(k, shape) * (
                0.02 if name.endswith("norm") else std)
        out.append(x.astype(c.param_dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ pieces
def _norm(h, g, eps):
    """RMSNorm with the gain ``1 + g``; ``h`` float32."""
    y = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return y * (1.0 + g.astype(jnp.float32))


def _qkv(x, p, c: EvaByteConfig):
    """(T, H) normed rows -> q, k, v (T, heads, d), unrotated."""
    cd = c.compute_dtype
    y = jnp.matmul(x.astype(cd), p["wqkv"].astype(cd))
    return tuple(y[:, i * c.hidden_size:(i + 1) * c.hidden_size].reshape(
        -1, c.num_attention_heads, c.head_dim) for i in range(3))


def _rest(h, o, p, c: EvaByteConfig):
    """The block after its attention: ``o`` (T, heads, d) through the
    output projection into the float32 stream ``h``, then the gated
    MLP."""
    cd = c.compute_dtype
    h = h + jnp.matmul(o.reshape(o.shape[0], -1).astype(cd),
                       p["wo"].astype(cd)).astype(jnp.float32)
    x = _norm(h, p["ffn_norm"], c.rms_norm_eps).astype(cd)
    y = jnp.matmul(jax.nn.silu(jnp.matmul(x, p["w_gate"].astype(cd)))
                   * jnp.matmul(x, p["w_up"].astype(cd)),
                   p["w_down"].astype(cd))
    return h + y.astype(jnp.float32)


def _embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def _layers(params, tokens, c: EvaByteConfig, attn_impl, open_at=None):
    """The blocks over one sequence ``tokens`` (S,), S whole windows: a
    loop over the layers, and in a layer a loop over the windows, so
    that a layer's temporaries are a window's and not a prompt's (a
    16,384-token prompt's gated MLP alone would be 1.1 GB of them).  A
    window's step projects and rotates its rows, pools its chunks into
    the layer's buffer of pooled pairs, attends over the buffer's
    earlier rows and its own tokens, and runs the rest of the block.

    Returns the float32 stream (S, H) and, with ``open_at`` (a prompt's
    length), what the cache keeps of every layer: the pooled pairs of
    all chunks ``(L, S // chunk, heads, d)`` twice and the own columns
    of the window the prompt ends in ``(L, window, heads, d)`` twice."""
    from apex_tpu.inference.kv_cache import open_window
    from apex_tpu.ops.eva import (
        eva_window_attention, pooled_capacity, summarise_chunks,
    )

    S, W = tokens.shape[0], c.window_size
    n, per = S // W, W // c.chunk_size
    heads, d, cd = c.num_attention_heads, c.head_dim, c.compute_dtype
    room = pooled_capacity(n, per, W)
    keep = open_at is not None
    if keep:
        open_w = open_window(open_at, S, c.cache_entry)[0] // W

    def layer(h, p):
        def window(carry, inp):
            kt_all, vt_all, k_open, v_open = carry
            h_w, w = inp
            pos = w * W + jnp.arange(W, dtype=jnp.int32)
            q, k, v = _qkv(_norm(h_w, p["attn_norm"], c.rms_norm_eps), p, c)
            rot = lambda x: apply_rope(x.transpose(1, 0, 2), pos,
                                       c.rope_theta).transpose(1, 0, 2)
            q, k = rot(q), rot(k)
            o = eva_window_attention(q, k, v, kt_all[:room], vt_all[:room],
                                     w * per, impl=attn_impl)
            kt, vt = summarise_chunks(k, v, p["phi"], p["mu"], c.chunk_size)
            put = lambda buf, x: jax.lax.dynamic_update_slice_in_dim(
                buf, x, w * per, axis=0)
            if keep:
                k_open = jnp.where(w == open_w, k, k_open)
                v_open = jnp.where(w == open_w, v, v_open)
            return (put(kt_all, kt), put(vt_all, vt), k_open, v_open), \
                _rest(h_w, o, p, c)

        # the buffer holds every window's pooled pairs (the cache keeps
        # the last window's too), padded to what the attention reads
        pooled = jnp.zeros((max(room, n * per), heads, d), cd)
        own = jnp.zeros((W if keep else 0, heads, d), cd)
        (kt, vt, k_open, v_open), h = jax.lax.scan(
            window, (pooled, pooled, own, own),
            (h.reshape(n, W, -1), jnp.arange(n, dtype=jnp.int32)))
        kept = (kt[:n * per], vt[:n * per], k_open, v_open) if keep else None
        return h.reshape(S, -1), kept

    return jax.lax.scan(layer, _embed(params, tokens), params["layers"])


def forward(params, tokens, config: EvaByteConfig, attn_impl: str = "auto"):
    """The full forward of one sequence: ``tokens`` (S,) -> float32
    logits (S, num_pred_heads * vocab_size), head-major (``fp32_logits``:
    the head's product runs in float32).  S is padded to whole windows
    inside (what follows a position never reaches it)."""
    c = config
    S = tokens.shape[0]
    pad = -S % c.window_size
    h, _ = _layers(params, jnp.pad(tokens, (0, pad)), c, attn_impl)
    x = _norm(h[:S], params["final_norm"], c.rms_norm_eps)
    return jnp.matmul(x, params["head"].astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)


def forward_decode(params, tokens, positions, active, pools, page_tables,
                   config: EvaByteConfig, attn_impl: str = "auto",
                   verify_width: int = 1, write_mask=None):
    """One token a slot over the windowed cache (the contract of
    :func:`apex_tpu.models.gpt.forward_decode`).

    ``pools``: ``"k"`` and ``"v"``, (layers, pages, heads, d,
    page_size), the allocator's pages of pooled columns and after them
    the slots' window buffers, and optionally ``"counters"``.  A layer
    writes the token's key and value into its slot's window
    (``apex_kv_write``), pools the chunk that the token closes, if it
    closes one, out of that window page (``apex_eva_summarise``) into
    the sequence's page of pooled columns (``apex_kv_write``, masked to
    the garbage page for every other slot), and attends over ONE page
    list, the closed windows' pooled pages and then the window's own
    (``apex_decode_attention``).  When a window closes nothing moves:
    the next token's live length in the buffer is one.  Returns
    ``(hidden (B, H) float32, pools)``, hidden final-normed."""
    from apex_tpu.inference.kv_cache import (
        COUNTERS, windowed_view, write_decode_pools,
    )
    from apex_tpu.ops.decode_attention_pallas import decode_attention
    from apex_tpu.ops.eva import eva_summarise

    c = config
    if verify_width != 1 or write_mask is not None:
        raise NotImplementedError(
            "the windowed cache takes one position a slot a step: "
            "speculative verify and chunked prefill are not built for "
            "this family (ROADMAP, Queue 2)")
    entry = c.cache_entry
    B = tokens.shape[0]
    page_size = pools["k"].shape[-1]
    num_pages = pools["k"].shape[1] - B * (c.window_size // page_size)
    positions = positions.astype(jnp.int32)
    tables, columns, lengths = windowed_view(
        page_tables, positions, active, jnp.arange(B), entry, page_size,
        num_pages)
    # the chunk the token closes: its page of the window buffer, its
    # first column there
    closing = active & (positions % c.chunk_size == c.chunk_size - 1)
    chunk_page = jnp.take_along_axis(
        tables, (columns // page_size)[:, None], axis=1, mode="clip")[:, 0]
    chunk_first = columns % page_size - (c.chunk_size - 1)

    def body(carry, inp):
        h, k_pool, v_pool = carry
        p, index = inp
        q, k, v = _qkv(_norm(h, p["attn_norm"], c.rms_norm_eps), p, c)
        q = apply_rope_at(q, positions, c.rope_theta)
        k = apply_rope_at(k, positions, c.rope_theta)
        k_pool, v_pool = write_decode_pools(
            (k_pool, v_pool), (k, v), tables, columns, active, layer=index,
            impl=attn_impl)
        kt, vt = eva_summarise(
            k_pool, v_pool, p["phi"], p["mu"], chunk_page, chunk_first,
            closing, index, c.chunk_size, impl=attn_impl)
        k_pool, v_pool = write_decode_pools(
            (k_pool, v_pool), (kt, vt), page_tables,
            positions // c.chunk_size, closing, layer=index, impl=attn_impl)
        o = decode_attention(q, k_pool, v_pool, tables, lengths,
                             impl=attn_impl, layer=index)
        return (_rest(h, o, p, c), k_pool, v_pool), None

    L = c.num_hidden_layers
    (h, k_pool, v_pool), _ = jax.lax.scan(
        body, (_embed(params, tokens), pools["k"], pools["v"]),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    out = dict(pools, k=k_pool, v=v_pool)
    if COUNTERS in pools:
        live = jnp.where(active, positions % c.window_size + 1, 0)
        out[COUNTERS] = pools[COUNTERS] + jnp.stack([
            jnp.sum(live), jnp.sum(lengths) - jnp.sum(live),
            jnp.sum(closing),
            jnp.sum(active & (positions % c.window_size
                              == c.window_size - 1))]).astype(jnp.int32)
    return _norm(h, params["final_norm"], c.rms_norm_eps), out


# ----------------------------------------------------------- served model
class EvaByteServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md)."""

    #: one position a slot a step: no speculative verify, no chunks
    multi_position = False
    #: the leaves that every served program reads only as
    #: ``leaf.astype(compute_dtype)``: the matrices.  Not the norm
    #: gains, ``phi`` and ``mu`` (float32 arithmetic), not ``embed``
    #: (gathered, then widened) or ``head`` (the sampling head's own)
    cast_once_leaves = ("wqkv", "wo", "w_gate", "w_up", "w_down")
    counter_names = COUNTER_NAMES

    def __init__(self, config: EvaByteConfig):
        self.config = config

    @property
    def max_positions(self):
        """Rotary positions, but the published context bounds them."""
        return self.config.max_position_embeddings

    def cache_spec(self) -> Dict[str, tuple]:
        return {"k": self.config.cache_entry, "v": self.config.cache_entry}

    def head(self, params):
        """The next byte's rows: the first ``vocab_size`` of the
        head-major head."""
        return params["head"][:self.config.vocab_size]

    def serving_params(self, params):
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt, S whole windows -> final-normed hidden
        (S, 1, H), every row the LAST prompt position's (the one a
        prefill samples from), and, a pool, ``(pooled columns (L, S // chunk, heads,
        d), the open window's own columns (L, window, heads, d))``:
        what :func:`apex_tpu.inference.kv_cache.write_prompt_windowed`
        takes."""
        c = self.config
        if prompt.shape[1] % c.window_size:
            raise ValueError(
                f"a prompt is padded to whole windows of {c.window_size} "
                f"(prefill_buckets and max_prompt_len): got "
                f"{prompt.shape[1]}")
        S = prompt.shape[1]
        h, (kt, vt, k, v) = _layers(params, prompt[0], c, attn_impl,
                                    open_at=prompt_len)
        # the one row a prefill reads, normed alone and shown in every
        # row: the whole (S, H) float32 normed stream is 268 MB at
        # 16,384 positions, for one row of it
        last = _norm(h[jnp.clip(prompt_len - 1, 0, S - 1)],
                     params["final_norm"], c.rms_norm_eps)
        return jnp.broadcast_to(last, (S, 1) + last.shape), \
            {"k": (kt, k), "v": (vt, v)}

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)
