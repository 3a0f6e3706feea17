"""GPT — the flagship transformer LM, Megatron-parallel on TPU.

Reference: ``apex/transformer/testing/standalone_gpt.py`` +
``standalone_transformer_lm.py`` (the Megatron LM used by the reference's
transformer tests): vocab-parallel embedding, pre-LN blocks with
column/row-parallel attention and MLP, causal fused softmax,
vocab-parallel cross entropy, sequence parallelism.

TPU-first structure:
- activations are ``(seq, batch, hidden)`` — the Megatron cross-stage
  contract (SURVEY §3.4) and the natural SP layout (seq is dim 0);
- layers are stacked with ``lax.scan`` over a leading layer axis so the
  program compiles once regardless of depth;
- per-layer activation checkpointing via ``jax.checkpoint`` (reference:
  tensor_parallel/random.py:237 CheckpointFunction);
- one code path: ``axis_name=None`` runs dense single-device; with an
  axis name the same functions run inside ``shard_map`` with
  q/k/v/fc1 column-sharded and proj/fc2 row-sharded.
"""

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.normalization import fused_layer_norm_affine
from apex_tpu.models._remat import remat_layer, validate_policy
from apex_tpu.observability.stepstats import offer as _stat_offer
from apex_tpu.transformer.functional import scaled_upper_triang_masked_softmax
from apex_tpu.transformer.tensor_parallel.cross_entropy import vocab_parallel_cross_entropy
from apex_tpu.transformer.tensor_parallel.layers import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_embedding,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    max_seq_len: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    # grouped-query attention (Megatron's knob name): number of kv-head
    # groups; None = one kv head per q head (standard MHA), 1 = MQA.
    num_query_groups: Optional[int] = None
    # "learned" (absolute table, the reference's standalone GPT) or
    # "rope" (rotary: unbounded length, composes with ring attention)
    position_embedding_type: str = "learned"
    rope_theta: float = 10000.0
    layernorm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    checkpoint_layers: bool = True
    # What layer remat may keep: "full" saves only the layer inputs (the
    # reference's tensor_parallel.random.checkpoint semantics — maximum
    # HBM savings, re-runs the whole layer forward in the backward);
    # "dots" saves MXU (matmul) outputs and recomputes only the cheap
    # elementwise/VPU work (LN, gelu, softmax) — trades a little HBM for
    # skipping the expensive recompute, often the best step time on TPU
    # where the backward is MXU-bound.  Ignored when checkpoint_layers
    # is False.
    remat_policy: str = "full"
    sequence_parallel: bool = False
    # memory-efficient attention core (ops.attention.flash_attention);
    # automatic when context parallelism is active
    use_flash_attention: bool = False
    # mixture-of-experts FFN (beyond the reference — SURVEY §2.4 "EP: No").
    # 0 = dense MLP.  Experts shard over the dp axis (EP rides DP) with
    # all_to_all token exchange; see transformer/expert_parallel.py.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    # Chunked fused LM-head + CE (ops/fused_ce.py): never materializes
    # the fp32 (S, B, V) logits — ~3.3 GB less HBM traffic per step at
    # 124M/S1024/B8 for one extra head-matmul of recompute in backward.
    # Falls back to the dense head when S % fused_ce_chunk != 0.
    fused_ce: bool = False
    fused_ce_chunk: int = 128
    # Pin the fused-CE implementation ("on" = Pallas kernels, "off" =
    # chunked scan, "interpret" = kernels via the Pallas interpreter);
    # None defers to the platform/env default.  Threaded (not an env
    # var) so an A/B never mutates process-global state under an
    # already-traced step function.
    fused_ce_impl: Optional[str] = None
    # Context-parallel ring attention only: issue each next hop's
    # ppermute BEFORE the current chunk's flash compute so the ICI hop
    # hides behind the per-chunk kernels (ring_attention's ``overlap``
    # knob — fp32-bitwise either way, so this is a pure schedule A/B).
    # Ignored when no cp axis is active.
    cp_overlap: bool = False

    def __post_init__(self):
        # validate at construction so every path (incl. checkpoint-
        # restored params that never call init_params) fails loudly on
        # a typo'd type — an unrecognized value would otherwise
        # silently train with NO positional information
        if self.position_embedding_type not in ("learned", "rope"):
            raise ValueError(
                f"position_embedding_type must be 'learned' or 'rope' "
                f"(got {self.position_embedding_type!r})"
            )
        validate_policy(self.remat_policy)

    @property
    def ffn(self):
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def moe(self):
        return self.moe_num_experts > 0

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        if self.num_query_groups is None:
            return self.num_attention_heads
        if self.num_query_groups < 1:
            raise ValueError(
                f"num_query_groups must be >= 1 (got {self.num_query_groups}); "
                "use None for standard multi-head attention"
            )
        return self.num_query_groups

    def served_model(self) -> "GPTServed":
        """This family's served-model adapter for ``apex_tpu.inference``."""
        return GPTServed(self)


def init_params(config: GPTConfig, key) -> Dict[str, Any]:
    """Global (unsharded) fp32 params; shard via PartitionSpecs from
    :func:`param_specs`."""
    H, F, L, V = config.hidden_size, config.ffn, config.num_layers, config.vocab_size
    k = jax.random.split(key, 12)
    std = 0.02
    init = lambda k, *s: jax.random.normal(k, s, jnp.float32) * std

    if config.num_attention_heads % config.kv_heads != 0:
        raise ValueError(
            f"num_attention_heads ({config.num_attention_heads}) must be "
            f"divisible by num_query_groups ({config.kv_heads})"
        )
    KV = config.kv_heads * config.head_dim  # kv projection width (GQA)
    params = {
        "embed": init(k[0], V, H),
        "layers": {
            "ln1_scale": jnp.ones((L, H)),
            "ln1_bias": jnp.zeros((L, H)),
            "wq": init(k[2], L, H, H),
            "wk": init(k[3], L, KV, H),
            "wv": init(k[4], L, KV, H),
            "bq": jnp.zeros((L, H)),
            "bk": jnp.zeros((L, KV)),
            "bv": jnp.zeros((L, KV)),
            "wo": init(k[5], L, H, H) / np.sqrt(2 * L),
            "bo": jnp.zeros((L, H)),
            "ln2_scale": jnp.ones((L, H)),
            "ln2_bias": jnp.zeros((L, H)),
        },
        "final_ln_scale": jnp.ones((H,)),
        "final_ln_bias": jnp.zeros((H,)),
    }
    if config.position_embedding_type == "learned":
        params["pos_embed"] = init(k[1], config.max_seq_len, H)
    if config.moe:
        from apex_tpu.transformer.expert_parallel import moe_init

        params["layers"]["moe"] = moe_init(
            k[8], H, F, config.moe_num_experts, layers=L
        )
    else:
        params["layers"].update(
            {
                "fc1": init(k[6], L, F, H),
                "fc1_b": jnp.zeros((L, F)),
                "fc2": init(k[7], L, H, F) / np.sqrt(2 * L),
                "fc2_b": jnp.zeros((L, H)),
            }
        )
    return params


def param_specs(config: GPTConfig, ep_axis: Optional[str] = None):
    """PartitionSpecs for shard_map in_specs (tp axis named 'tp').

    Column-parallel weights shard the output dim, row-parallel the input
    dim; embedding shards the vocab (reference layers.py:174,460,645).
    With MoE, expert weights shard over ``ep_axis`` (usually 'dp').
    """
    from jax.sharding import PartitionSpec as P

    col = P(None, "tp", None)
    colb = P(None, "tp")
    row = P(None, None, "tp")
    rep2 = P(None, None)
    layers = {
        "ln1_scale": rep2,
        "ln1_bias": rep2,
        "wq": col,
        "wk": col,
        "wv": col,
        "bq": colb,
        "bk": colb,
        "bv": colb,
        "wo": row,
        "bo": rep2,
        "ln2_scale": rep2,
        "ln2_bias": rep2,
    }
    if config.moe:
        from apex_tpu.transformer.expert_parallel import moe_param_specs

        # ep_axis None = replicated (single-device / no EP)
        layers["moe"] = moe_param_specs(ep_axis, layers=True)
    else:
        layers.update({"fc1": col, "fc1_b": colb, "fc2": row, "fc2_b": rep2})
    specs = {
        "embed": P("tp", None),
        "layers": layers,
        "final_ln_scale": P(None),
        "final_ln_bias": P(None),
    }
    if config.position_embedding_type == "learned":
        specs["pos_embed"] = P(None, None)
    return specs


def _add_pos_embed(x, pos_table, config: GPTConfig, cp_axis):
    """Add the learned position table to (S, B, H) activations — the
    LOCAL sequence chunk's rows when the sequence is cp-sharded.  No-op
    under rope (positions enter as q/k rotations in attention)."""
    if config.position_embedding_type != "learned":
        return x
    S = x.shape[0]
    if cp_axis is not None:
        start = jax.lax.axis_index(cp_axis) * S
        pos = jax.lax.dynamic_slice_in_dim(pos_table, start, S, axis=0)
    else:
        pos = pos_table[:S]
    return x + pos[:, None, :]


def _col_proj(x, w, b, axis_name, sp=False):
    """Column-parallel projection, dense when ``axis_name`` is None —
    the ONE dispatch both the training attention block and the decode
    twin (:func:`forward_decode`) use, so the dense/tp seam cannot
    drift between them."""
    if axis_name is None:
        return jnp.matmul(x, w.T.astype(x.dtype)) + b.astype(x.dtype)
    return column_parallel_linear(
        x, w, b, gather_output=False, sequence_parallel_enabled=sp,
        axis_name=axis_name)


def _attention(x, p, config: GPTConfig, axis_name, n_local_heads, cp_axis=None,
               collect_kv=False):
    """Self attention with column-parallel QKV and row-parallel output
    proj (reference standalone_transformer_lm.py ParallelAttention).
    The core is selectable: fused-softmax einsum (default), flash
    attention, or ring attention when the sequence is sharded over
    ``cp_axis``.  With grouped-query attention
    (``config.num_query_groups``) k/v carry fewer heads; the flash
    kernel reads group-shared kv blocks directly, the einsum/ring paths
    repeat heads."""
    S = x.shape[0] * (1 if not (axis_name and config.sequence_parallel) else jax.lax.axis_size(axis_name))
    B = x.shape[1]
    hd = config.head_dim
    tp = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    if config.kv_heads % tp != 0:
        raise ValueError(
            f"num_query_groups ({config.kv_heads}) must be divisible by the "
            f"tensor-parallel size ({tp}): kv heads shard over tp"
        )
    n_local_kv = config.kv_heads // tp
    sp = config.sequence_parallel and axis_name is not None

    def col(x_, w, b):
        return _col_proj(x_, w, b, axis_name, sp=sp)

    q = col(x, p["wq"], p["bq"])
    k = col(x, p["wk"], p["bk"])
    v = col(x, p["wv"], p["bv"])

    # (S, B, local_heads*hd) → (B, nh, S, hd)
    def heads(t, nh):
        return t.reshape(S, B, nh, hd).transpose(1, 2, 0, 3)

    q, k, v = heads(q, n_local_heads), heads(k, n_local_kv), heads(v, n_local_kv)
    if config.position_embedding_type == "rope":
        from apex_tpu.ops.rope import apply_rope

        # global positions of the LOCAL chunk: with context parallelism
        # each rank rotates its own chunk before k/v ride the ring
        start = 0 if cp_axis is None else jax.lax.axis_index(cp_axis) * S
        positions = start + jnp.arange(S)
        q = apply_rope(q, positions, config.rope_theta)
        k = apply_rope(k, positions, config.rope_theta)
    # the prefill path captures each layer's post-RoPE k/v (B, kv, S, hd)
    # BEFORE any head repeat, so the paged cache stores the group-shared
    # GQA heads exactly as the decode kernels expect them
    kv_out = (k, v) if collect_kv else None
    if cp_axis is not None:
        from apex_tpu.ops.attention import repeat_kv_heads
        from apex_tpu.transformer.context_parallel import ring_attention

        # the ring walks matched head counts; GQA repeats before it
        k, v = repeat_kv_heads(q, k, v)
        ctx = ring_attention(q, k, v, cp_axis, causal=True,
                             overlap=config.cp_overlap).astype(v.dtype)
    elif config.use_flash_attention:
        from apex_tpu.ops.attention import flash_attention

        ctx = flash_attention(q, k, v, causal=True)
    else:
        from apex_tpu.ops.attention import repeat_kv_heads

        k, v = repeat_kv_heads(q, k, v)
        scores = jnp.einsum("bnsh,bnth->bnst", q, k) / np.sqrt(hd)
        probs = scaled_upper_triang_masked_softmax(scores, 1.0)
        ctx = jnp.einsum("bnst,bnth->bnsh", probs.astype(v.dtype), v)
    ctx = ctx.transpose(2, 0, 1, 3).reshape(S, B, n_local_heads * hd)

    if axis_name is None:
        out = jnp.matmul(ctx, p["wo"].T.astype(ctx.dtype)) + p["bo"].astype(ctx.dtype)
    else:
        out = row_parallel_linear(
            ctx, p["wo"], p["bo"], input_is_parallel=True,
            sequence_parallel_enabled=sp, axis_name=axis_name,
        )
    return (out, kv_out) if collect_kv else out


def _mlp(x, p, config: GPTConfig, axis_name):
    sp = config.sequence_parallel and axis_name is not None
    if axis_name is None:
        h = jnp.matmul(x, p["fc1"].T.astype(x.dtype)) + p["fc1_b"].astype(x.dtype)
        h = jax.nn.gelu(h, approximate=True)
        return jnp.matmul(h, p["fc2"].T.astype(h.dtype)) + p["fc2_b"].astype(h.dtype)
    h = column_parallel_linear(
        x, p["fc1"], p["fc1_b"], gather_output=False, sequence_parallel_enabled=sp, axis_name=axis_name
    )
    h = jax.nn.gelu(h, approximate=True)
    return row_parallel_linear(
        h, p["fc2"], p["fc2_b"], input_is_parallel=True, sequence_parallel_enabled=sp, axis_name=axis_name
    )


def _moe_mlp(x, p, config: GPTConfig, ep_axis):
    """Expert-parallel FFN (beyond the reference); x: (S, B, H).
    Experts shard over ``ep_axis``; tp ranks compute replicated."""
    from apex_tpu.transformer.expert_parallel import moe_ffn

    out, aux = moe_ffn(
        x,
        p["moe"],
        top_k=config.moe_top_k,
        capacity_factor=config.moe_capacity_factor,
        ep_axis=ep_axis,
    )
    return out, aux


def _layer(x, p, config: GPTConfig, axis_name, n_local_heads, cp_axis=None,
           ep_axis=None, collect_kv=False):
    """Returns (x, aux) — aux is the MoE load-balancing loss (0 when
    dense).  With ``collect_kv`` the aux slot becomes ``(aux, k, v)``
    with the layer's post-RoPE keys/values (the prefill capture)."""
    H = config.hidden_size
    ln1 = fused_layer_norm_affine(x, p["ln1_scale"], p["ln1_bias"], (H,), config.layernorm_eps)
    attn = _attention(ln1.astype(config.compute_dtype), p, config, axis_name,
                      n_local_heads, cp_axis, collect_kv=collect_kv)
    kv = None
    if collect_kv:
        attn, kv = attn
    x = x + attn
    ln2 = fused_layer_norm_affine(x, p["ln2_scale"], p["ln2_bias"], (H,), config.layernorm_eps)
    if config.moe:
        h, aux = _moe_mlp(ln2.astype(config.compute_dtype), p, config, ep_axis)
    else:
        h = _mlp(ln2.astype(config.compute_dtype), p, config, axis_name)
        aux = jnp.float32(0.0)
    x = x + h
    if collect_kv:
        return x, (aux, kv[0], kv[1])
    return x, aux


def _embed_segment(embed_w, pos_w, tokens, config: GPTConfig, axis_name,
                   cp_axis):
    """Forward segment 1: token lookup + learned positions, cast to the
    compute dtype, SP scatter.  ``(B, S)`` tokens → ``(S, B, H)``.

    The three ``_*_segment`` functions are the seam the backward-
    overlapped gradient sync (``make_train_step(overlap_grad_sync=
    True)``) cuts the model at: each segment gets its own ``jax.vjp`` so
    bucket collectives can issue between segment backwards.  They are
    the SAME functions ``gpt_forward`` composes, so the overlapped
    build's per-op arithmetic is definitionally identical to the
    monolithic one — only collective placement moves."""
    if axis_name is None:
        emb = jnp.take(embed_w, tokens, axis=0)  # (B, S, H)
    else:
        emb = vocab_parallel_embedding(tokens, embed_w, axis_name=axis_name)
    x = _add_pos_embed(emb.transpose(1, 0, 2), pos_w, config, cp_axis)
    x = x.astype(config.compute_dtype)
    if config.sequence_parallel and axis_name is not None:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            scatter_to_sequence_parallel_region,
        )

        x = scatter_to_sequence_parallel_region(x, axis_name)
    return x


def _layers_segment(layers_p, x, config: GPTConfig, axis_name, cp_axis,
                    ep_axis, return_kv=False):
    """Forward segment 2: the stacked-layer ``lax.scan`` — returns
    ``(x, ys)`` exactly as the scan does.  Because layers are scanned
    over a stacked leading axis, every ``layers.*`` leaf's gradient
    materializes only when the WHOLE scan backward finishes: the scan
    is one readiness stage, not L of them."""
    tp = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    n_local_heads = config.num_attention_heads // tp
    layer = partial(
        _layer, config=config, axis_name=axis_name,
        n_local_heads=n_local_heads, cp_axis=cp_axis, ep_axis=ep_axis,
        collect_kv=return_kv,
    )
    if config.checkpoint_layers:
        layer = remat_layer(layer, config.remat_policy)

    # _layer's (carry, lp) -> (x, aux) is exactly the scan contract
    return jax.lax.scan(layer, x, layers_p)


def _head_segment(x, ln_scale, ln_bias, config: GPTConfig, axis_name):
    """Forward segment 3: SP gather, final LayerNorm, copy-to-region.
    Returns pre-head hidden states ``(S, B, H)``."""
    if config.sequence_parallel and axis_name is not None:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            gather_from_sequence_parallel_region,
        )

        # tensor_parallel_output_grad=False: the head's dx is psum'd by the
        # copy-to-region below, so the backward here must split, not
        # reduce-scatter (reference mappings.py:236-250)
        x = gather_from_sequence_parallel_region(x, axis_name, False)

    x = fused_layer_norm_affine(
        x, ln_scale, ln_bias, (config.hidden_size,), config.layernorm_eps
    )
    # tied LM head over the (local) vocab shard.  The copy-to-region is
    # load-bearing: its backward all-reduces dx across vocab shards
    # (Megatron parallel_lm_logits; reference layers.py:141-156 pairing).
    if axis_name is not None:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            copy_to_tensor_model_parallel_region,
        )

        x = copy_to_tensor_model_parallel_region(x, axis_name)
    return x


# Gradient-readiness stage of each top-level param group under the
# segmented (overlapped) backward: the head backward (stage 0) yields
# the final-LN cotangents, the scan backward (stage 1) every stacked
# ``layers.*`` leaf at once, and the embed backward (stage 2) the
# positions plus the tied embedding's lookup half (its head half
# arrives at stage 0 but the leaf is only COMPLETE — summable — after
# stage 2, so the tied embed is last-ready by construction).
_OVERLAP_STAGES = {"final_ln_scale": 0, "final_ln_bias": 0, "layers": 1,
                   "embed": 2, "pos_embed": 2}


def gpt_forward(
    params, tokens, config: GPTConfig, axis_name: Optional[str] = None,
    cp_axis: Optional[str] = None, ep_axis: Optional[str] = None,
    return_aux: bool = False, return_hidden: bool = False,
    return_kv: bool = False,
):
    """tokens (B, S) → logits.

    With ``axis_name``: runs inside shard_map; returns vocab-LOCAL logits
    ``(S, B, V/tp)``.  Without: dense logits ``(S, B, V)``.
    With ``cp_axis`` (context parallelism — a capability beyond the
    reference): tokens are the LOCAL sequence chunk, attention is ring
    attention over the axis, positions are globally offset.
    With MoE (``config.moe_num_experts > 0``), ``ep_axis`` shards the
    experts (EP rides DP); ``return_aux=True`` additionally returns the
    summed load-balancing loss.
    With ``return_kv=True`` a trailing ``(k, v)`` pair is appended —
    each ``(L, B, kv_heads_local, S, head_dim)``, every layer's
    post-RoPE keys/values — the prefill capture the paged-KV serving
    path (:mod:`apex_tpu.inference`) writes into its page pool.
    """
    if cp_axis is not None and config.sequence_parallel:
        raise ValueError("sequence_parallel (tp) and context parallelism both shard "
                         "the sequence; enable one")
    if config.moe and config.sequence_parallel:
        raise ValueError("MoE with Megatron sequence parallelism is not supported: "
                         "expert grads would need an extra tp-psum; use cp instead")
    x = _embed_segment(params["embed"], params.get("pos_embed"), tokens,
                       config, axis_name, cp_axis)
    x, ys = _layers_segment(params["layers"], x, config, axis_name, cp_axis,
                            ep_axis, return_kv=return_kv)
    if return_kv:
        aux_per_layer, kv_k, kv_v = ys
        kv = (kv_k, kv_v)
    else:
        aux_per_layer, kv = ys, None
    aux = jnp.sum(aux_per_layer)

    def _out(*vals):
        return vals + (kv,) if return_kv else (
            vals if len(vals) > 1 else vals[0])

    x = _head_segment(x, params["final_ln_scale"], params["final_ln_bias"],
                      config, axis_name)
    if return_hidden:
        # pre-head activations for the chunked fused CE (fused_ce.py);
        # the copy-to-region above already carries the head's dx
        # all-reduce, so the fused op's local dx composes unchanged
        return _out(x, aux) if return_aux else _out(x)  # (S, B, H)
    logits = jnp.matmul(x.astype(jnp.float32), params["embed"].T.astype(jnp.float32))
    if return_aux:
        return _out(logits, aux)  # (S, B, V_local), scalar
    return _out(logits)  # (S, B, V_local)


def lm_head_loss(x, embed, targets, config: GPTConfig,
                 axis_name: Optional[str] = None):
    """Per-token CE ``(S, B)`` of the tied LM head on pre-head
    activations ``x`` (post final-LN, post copy-to-region in tp mode).

    The ONE dispatch between the dense head (fp32 logits matmul + CE)
    and the chunked fused head (ops/fused_ce.py) — both ``gpt_loss``
    and the pipeline post-stage consume it, so the fallback condition
    and head semantics cannot drift between the two training paths."""
    if config.fused_ce and targets.shape[0] % config.fused_ce_chunk == 0:
        from apex_tpu.ops.fused_ce import fused_lm_head_ce

        return fused_lm_head_ce(x, embed, targets,
                                config.fused_ce_chunk, axis_name,
                                config.fused_ce_impl)
    logits = jnp.matmul(x.astype(jnp.float32), embed.T.astype(jnp.float32))
    if axis_name is None:
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # clamp: bare take_along_axis WRAPS negative ids and NaN-fills
        # past-V ones under jit — the fused scan and Pallas heads both
        # clamp, and all three paths must share one out-of-range semantic
        t_cl = jnp.clip(targets, 0, logits.shape[-1] - 1)
        tgt = jnp.take_along_axis(logits, t_cl[..., None], axis=-1)[..., 0]
        return lse - tgt
    return vocab_parallel_cross_entropy(logits, targets, 0.0, axis_name)


def _spmd_ce_fwd_impl(logits, target):
    """Dense spelling of the Megatron vocab-parallel CE (see
    ``transformer/tensor_parallel/cross_entropy._fwd_impl``) with every
    collective dropped — max/sum/gather run over the FULL vocab axis.
    Under ``jit`` with the vocab dim sharded, XLA's SPMD partitioner
    re-derives exactly the collectives the shard_map version spells by
    hand (local max + all-reduce-max, masked local gather + all-reduce,
    local sum-exp + all-reduce), which is what makes the
    ``spmd="auto"`` step's loss bitwise-comparable to the shard_map
    oracle — the ``logsumexp`` head in :func:`lm_head_loss` is a
    DIFFERENT formula with a different autodiff backward and can never
    match it."""
    lmax = jnp.max(logits, axis=-1)
    logits = logits - lmax[..., None]
    vocab = logits.shape[-1]
    mask = (target < 0) | (target >= vocab)
    clipped = jnp.clip(target, 0, vocab - 1)
    predicted = jnp.take_along_axis(logits, clipped[..., None], axis=-1)[..., 0]
    predicted = jnp.where(mask, 0.0, predicted)
    exp_logits = jnp.exp(logits)
    sum_exp = jnp.sum(exp_logits, axis=-1)
    loss = jnp.log(sum_exp) - predicted
    softmax = exp_logits / sum_exp[..., None]
    return loss, (softmax, mask, clipped)


@jax.custom_vjp
def _spmd_vocab_ce(logits, target):
    """Per-token CE ``(S, B)`` on fp32 logits ``(S, B, V)`` — the
    GSPMD-native head of :func:`make_train_step` ``spmd="auto"``.  The
    backward is the Megatron ``softmax - onehot`` custom vjp, matching
    ``vocab_parallel_cross_entropy`` term for term so the partitioned
    program and the shard_map oracle run the same arithmetic."""
    return _spmd_ce_fwd_impl(logits, target)[0]


def _spmd_ce_fwd(logits, target):
    return _spmd_ce_fwd_impl(logits, target)


def _spmd_ce_bwd(res, g):
    softmax, mask, clipped = res
    vocab = softmax.shape[-1]
    update = (~mask).astype(softmax.dtype)
    onehot = jax.nn.one_hot(clipped, vocab, dtype=softmax.dtype) * update[..., None]
    grad = (softmax - onehot) * g[..., None]
    return grad.astype(softmax.dtype), None


_spmd_vocab_ce.defvjp(_spmd_ce_fwd, _spmd_ce_bwd)


def gpt_loss_spmd(params, tokens, targets, config: GPTConfig):
    """Mean causal-LM loss of the GSPMD-native step: the DENSE forward
    (no axis names, no collectives — XLA places them from the sharding
    annotations) with the Megatron-formulation CE head
    (:func:`_spmd_vocab_ce`)."""
    hidden = gpt_forward(params, tokens, config, None, None, None,
                         return_hidden=True)
    logits = jnp.matmul(hidden.astype(jnp.float32),
                        params["embed"].T.astype(jnp.float32))
    return jnp.mean(_spmd_vocab_ce(logits, targets.transpose(1, 0)))


def forward_decode(params, tokens, positions, active, kv_pools, page_tables,
                   config: GPTConfig, axis_name: Optional[str] = None,
                   attn_impl: str = "auto", verify_width: int = 1,
                   write_mask=None):
    """Single-token decode forward over the paged KV cache.

    The serving-side twin of :func:`gpt_forward`: same weights, same
    block expression (the LN/projection/MLP helpers are shared, run at
    sequence length 1), but attention is single-query over the page
    pool (:func:`apex_tpu.ops.decode_attention_pallas.decode_attention`)
    and each layer first writes the current token's post-RoPE k/v
    into its pages, in place
    (:func:`apex_tpu.inference.kv_cache.write_decode_kv`).  The stacked
    pools are the layer loop's carry and the layer index rides to both
    kernels as a scalar: no layer is sliced out, no new pool stacked.
    Every shape is static — batch is the slot count,
    the page-table block is (B, pages_per_seq) — so the jitted step
    compiles ONCE and is reused across all cache lengths and batch
    occupancies (inactive slots are masked, their writes land on the
    reserved garbage page).

    ``tokens``/``positions``/``active``: (B,) current token ids, their
    0-based positions, and the slot-live mask.  ``kv_pools``: the
    ``{"k", "v"}`` pools from :func:`apex_tpu.inference.kv_cache
    .alloc_pools` (kv heads LOCAL under tp).  ``page_tables``:
    (B // verify_width, P) int32.  With ``axis_name`` the projections
    run column/row-parallel inside shard_map exactly as in training
    (kv heads shard over tp, so each rank's pool carries its local
    heads).

    ``verify_width`` W > 1 is the multi-position layout (speculative
    verification, a prefill chunk): rows come in groups of W
    CONSECUTIVE positions of one sequence sharing a page-table row.
    Each layer first scatters ALL W rows' post-RoPE k/v into the pages,
    then every row attends under its OWN causal length (``positions[i]
    + 1``) — row j of a group reads the k/v rows 0..j wrote this very
    step, so the group is exactly a causal block over the paged cache.
    W is static: one compile per width, reused across every
    occupancy / draft-hit / chunk-phase mix.  ``write_mask`` (defaults
    to ``active``) narrows WHICH rows scatter their k/v — attention
    liveness stays ``active`` — so a chunk can recompute a
    shared-prefix position's hidden state without rewriting the shared
    page (the COW discipline).

    Returns ``(hidden, new_pools)`` — hidden (B, H) is the pre-head
    activation (post final-LN, post copy-to-region under tp), the same
    contract as ``gpt_forward(return_hidden=True)``; the caller owns
    the head (fused sampling for serving, the fp32 logits matmul for
    the parity band).
    """
    from apex_tpu.inference.kv_cache import write_decode_kv
    from apex_tpu.ops.decode_attention_pallas import decode_attention

    if config.moe:
        raise NotImplementedError(
            "this GPT block's MoE (capacity factor, dropped tokens) has "
            "no decode path; the served expert layer is "
            "expert_parallel.held_experts_ffn, which models.mla_moe "
            "uses (docs/moe.md)")
    if config.sequence_parallel:
        raise ValueError(
            "sequence_parallel shards the sequence axis; a decode step "
            "is one token — build the decode config without it")
    B = tokens.shape[0]
    H = config.hidden_size
    hd = config.head_dim
    tp = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    if config.kv_heads % tp != 0:
        raise ValueError(
            f"num_query_groups ({config.kv_heads}) must be divisible by "
            f"the tensor-parallel size ({tp}): kv heads (and the KV page "
            "pools) shard over tp")
    n_local_heads = config.num_attention_heads // tp
    n_local_kv = config.kv_heads // tp
    positions = positions.astype(jnp.int32)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    if write_mask is None:
        write_mask = active
    if B % verify_width != 0:
        raise ValueError(
            f"batch ({B}) must be a multiple of verify_width "
            f"({verify_width})")

    if axis_name is None:
        emb = jnp.take(params["embed"], tokens, axis=0)  # (B, H)
    else:
        emb = vocab_parallel_embedding(
            tokens[:, None], params["embed"], axis_name=axis_name)[:, 0]
    x = emb[None]  # (1, B, H) — the (S, B, H) layout at S = 1
    if config.position_embedding_type == "learned":
        pos = jnp.take(params["pos_embed"],
                       jnp.clip(positions, 0, config.max_seq_len - 1), axis=0)
        x = x + pos[None]
    x = x.astype(config.compute_dtype)

    # the pools are the loop's CARRY and the layer index rides to the
    # two kernels as a scalar: nothing slices a layer out, nothing
    # stacks a new pool (kv_cache's module doc has the why)
    def layer(carry, inp):
        x, k_pool, v_pool = carry
        p, li = inp
        ln1 = fused_layer_norm_affine(
            x, p["ln1_scale"], p["ln1_bias"], (H,), config.layernorm_eps)
        h = ln1.astype(config.compute_dtype)
        col = lambda w, b: _col_proj(h, w, b, axis_name)  # noqa: E731
        q = col(p["wq"], p["bq"])[0].reshape(B, n_local_heads, hd)
        k = col(p["wk"], p["bk"])[0].reshape(B, n_local_kv, hd)
        v = col(p["wv"], p["bv"])[0].reshape(B, n_local_kv, hd)
        if config.position_embedding_type == "rope":
            from apex_tpu.ops.rope import apply_rope_at

            q = apply_rope_at(q, positions, config.rope_theta)
            k = apply_rope_at(k, positions, config.rope_theta)
        k_pool, v_pool = write_decode_kv(
            k_pool, v_pool, k, v, page_tables, positions, write_mask,
            layer=li, width=verify_width, impl=attn_impl)
        ctx = decode_attention(q, k_pool, v_pool, page_tables, lengths,
                               impl=attn_impl, width=verify_width, layer=li)
        ctx = ctx.astype(config.compute_dtype).reshape(
            1, B, n_local_heads * hd)
        if axis_name is None:
            attn = jnp.matmul(ctx, p["wo"].T.astype(ctx.dtype)) \
                + p["bo"].astype(ctx.dtype)
        else:
            attn = row_parallel_linear(
                ctx, p["wo"], p["bo"], input_is_parallel=True,
                sequence_parallel_enabled=False, axis_name=axis_name)
        x = x + attn
        ln2 = fused_layer_norm_affine(
            x, p["ln2_scale"], p["ln2_bias"], (H,), config.layernorm_eps)
        x = x + _mlp(ln2.astype(config.compute_dtype), p, config, axis_name)
        return (x, k_pool, v_pool), None

    (x, new_k, new_v), _ = jax.lax.scan(
        layer, (x, kv_pools["k"], kv_pools["v"]),
        (params["layers"], jnp.arange(config.num_layers, dtype=jnp.int32)))
    x = fused_layer_norm_affine(
        x, params["final_ln_scale"], params["final_ln_bias"], (H,),
        config.layernorm_eps)
    if axis_name is not None:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            copy_to_tensor_model_parallel_region,
        )

        x = copy_to_tensor_model_parallel_region(x, axis_name)
    return x[0], {"k": new_k, "v": new_v}


class GPTServed:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md): the cache spec, the
    prefill, the decode forward, the head matrix and the tree to serve
    from."""

    #: the decode forward takes ``verify_width`` > 1 (speculative
    #: verify, prefill chunks)
    multi_position = True
    counter_names = ()
    #: the leaves of ``params["layers"]`` that :func:`gpt_forward` and
    #: :func:`forward_decode` (dense: serving is tp = 1) read ONLY as
    #: ``leaf.astype(compute_dtype)``, and that are worth a program:
    #: the six stacked matrices (``_col_proj``, ``_attention``,
    #: ``_mlp``).  Not ``embed`` (the lookup adds ``pos_embed`` in the
    #: parameters' dtype before the cast, and the head reads it as it
    #: is), not ``pos_embed``, not a LayerNorm gain or bias (the norm
    #: reads them as they are); the six projection biases are read the
    #: same way but are a thousandth of the bytes
    cast_once_leaves = ("wq", "wk", "wv", "wo", "fc1", "fc2")

    def __init__(self, config: GPTConfig):
        if config.moe:
            raise NotImplementedError(
                "this GPT block's MoE has no decode path (forward_decode)")
        self.config = config

    @property
    def max_positions(self):
        """Positions a learned table holds; None for rotary."""
        c = self.config
        return c.max_seq_len if c.position_embedding_type == "learned" \
            else None

    def cache_spec(self):
        c = self.config  # single-process serving: tp = 1
        shape = (c.num_layers, c.kv_heads, c.head_dim)
        return {"k": shape, "v": shape}

    def head(self, params):
        return params["embed"]

    def serving_params(self, params):
        """The tree to give the served programs: :attr:`cast_once_leaves`
        in ``compute_dtype``, every other leaf the array it was
        (:func:`apex_tpu.inference.decode.cast_once`)."""
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt -> hidden (S, 1, H) and the post-RoPE
        keys and values by pool name, (L, S, KVH, hd) each."""
        del prompt_len, attn_impl  # the training forward pads causally
        hidden, kv = gpt_forward(params, prompt, self.config,
                                 return_hidden=True, return_kv=True)
        k_stack, v_stack = kv  # (L, 1, KVH, S, hd)
        return hidden, {"k": k_stack[:, 0].transpose(0, 2, 1, 3),
                        "v": v_stack[:, 0].transpose(0, 2, 1, 3)}

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)


def sp_grad_sync(grads, axis_name: str):
    """Sequence-parallel gradient sync: params consumed in the
    seq-sharded region (LN scales/biases and row-parallel biases) see only
    this rank's tokens in backward, so their grads must be summed over tp
    (reference: apex/transformer/layers/layer_norm.py:26 marking +
    Megatron's allreduce_sequence_parallel_gradients)."""
    sp_keys = {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "bo", "fc2_b"}
    layers = dict(grads["layers"])
    for k in sp_keys:
        layers[k] = jax.lax.psum(layers[k], axis_name)
    return {**grads, "layers": layers}


def clip_sumsq_reduce(specs):
    """The cross-rank Σx² agreement for a global-l2 grad clip inside a
    shard_map step.

    A leaf whose PartitionSpec names mesh axes holds only its LOCAL
    shard of the grads, so the true global norm needs its Σx² psummed
    over exactly those axes — while replicated leaves (every rank holds
    the full grad) must NOT be psummed, or each mesh axis would
    multiply their contribution by its size.  Group the leaves by the
    axis set their spec names, sum each group locally, psum the
    sharded groups over their axes, add.  (Megatron's
    ``clip_grad_norm_`` does the same split via the
    ``tensor_model_parallel`` param attribute; here the PartitionSpecs
    already carry the fact.)  Returns ``reduce(per_leaf_sumsq) ->
    total_sumsq`` for the optimizer's ``sumsq_reduce=`` hook."""
    from jax.sharding import PartitionSpec

    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))

    def axes_of(p):
        axes = []
        for e in tuple(p):
            if isinstance(e, (tuple, list)):
                axes.extend(a for a in e if a)
            elif e is not None:
                axes.append(e)
        return frozenset(axes)

    groups: Dict[frozenset, list] = {}
    for i, sp in enumerate(spec_leaves):
        groups.setdefault(axes_of(sp), []).append(i)

    def reduce(sq):
        if len(sq) != len(spec_leaves):
            raise ValueError(
                f"clip_sumsq_reduce built for {len(spec_leaves)} param "
                f"leaves got {len(sq)} sumsq values — param tree and "
                f"spec tree diverged")
        total = jnp.float32(0.0)
        for axes in sorted(groups, key=lambda a: sorted(a)):
            part = jnp.stack([sq[i] for i in groups[axes]]).sum()
            if axes:
                part = jax.lax.psum(part, tuple(sorted(axes)))
            total = total + part
        return total

    return reduce


def _check_zero_axis(zero_opt, optimizer, dp_axis):
    """A ZeRO optimizer's collectives run over ITS ``axis_name`` (or
    hierarchical ``dp_axes``); the step builder's grad calculus (skip
    the dp pmean, add dp to the finite-vote axes) is keyed on
    ``dp_axis``.  A mismatch would silently double- or un-sync the
    grads, so fail at build time.  A hierarchical step
    (``dp_axis=(outer, inner)``) needs an optimizer constructed with
    the SAME ``dp_axes`` split — its two-hop reduce-scatter owns both
    hops — and a hierarchical optimizer refuses a flat step."""
    if not zero_opt:
        return
    opt_axes = getattr(optimizer, "dp_axes", None)
    if isinstance(dp_axis, (tuple, list)):
        dp_axis = tuple(dp_axis)
        if opt_axes is None or tuple(opt_axes) != dp_axis:
            have = (tuple(opt_axes) if opt_axes is not None
                    else getattr(optimizer, "axis_name", None))
            raise ValueError(
                f"the train step's dp axis is the hierarchical split "
                f"{dp_axis!r} but the ZeRO optimizer syncs over "
                f"{have!r}; construct it with dp_axes={dp_axis!r} (the "
                "optimizer owns both hops of the grad sync)")
        return
    if opt_axes is not None:
        raise ValueError(
            f"ZeRO optimizer was built for the hierarchical dp split "
            f"{tuple(opt_axes)!r} but the train step's dp axis is the "
            f"flat {dp_axis!r}; pass dp_axis={tuple(opt_axes)!r} to "
            "make_train_step (or drop the optimizer's dp_axes)")
    opt_axis = getattr(optimizer, "axis_name", None)
    if dp_axis is None or opt_axis != dp_axis:
        raise ValueError(
            f"ZeRO optimizer shards over axis {opt_axis!r} but the train "
            f"step's dp axis is {dp_axis!r}; pass axis_name={dp_axis!r} "
            "to the optimizer (or dp_axis= to the step builder)")


def _clip_reduce_for(optimizer, clip_grad_norm, specs):
    """Shared clip wiring for both step builders: validate the
    optimizer can fold the clip into its fused grad pass, and build
    the spec-driven cross-rank sumsq agreement.  Returns None when no
    clipping is requested."""
    if clip_grad_norm is None:
        return None
    if not getattr(optimizer, "supports_update_scaled", False):
        raise ValueError(
            "clip_grad_norm needs an engine optimizer (OptimizerBase "
            "subclass) — the clip folds into its fused grad pass")
    return clip_sumsq_reduce(specs)


def _apply_scaled_update(loss_scaler, scaler_state, grads, optimizer,
                         opt_state, params, sync_axes,
                         step_guard=None, guard_state=None,
                         clip_grad_norm=None, clip_sumsq=None,
                         presynced=None):
    """The shared unscale → found_inf vote → predicated step → scale
    update tail of both scaled train steps (reference §3.2 ctx-exit:
    ``apex/amp/handle.py:119-158`` + the model-parallel found_inf
    agreement of ``apex/transformer/amp/grad_scaler.py:49,102``).

    With an engine optimizer (:class:`apex_tpu.optimizers.base
    .OptimizerBase`) the whole tail is ONE fused pass over the grad
    buckets — unscale, optional global-l2 clip, and the finite vote
    fold into the optimizer's own grad read (``update_scaled``) instead
    of three separate tree sweeps.  The ZeRO optimizers take the same
    fused route: their ``update_scaled`` folds the unscale, the clip
    (Σx² psummed over the dp shards and, via ``clip_sumsq``, the model
    axes), and the vote into the per-bucket reduce-scattered grad read.
    Optimizers without the capability (``supports_update_scaled``
    False, e.g. contrib ``FusedAdamSWA``) keep the explicit sweep
    composition.

    With a ``step_guard`` (:class:`apex_tpu.resilience.StepGuard`) the
    same agreed predicate also feeds the guard's device-side bad-step
    accounting, and the tuple grows a new guard state — ONE vote drives
    the optimizer skip, the scaler hysteresis, and the abort budget."""
    from apex_tpu.transformer.amp.grad_scaler import sync_found_inf

    if getattr(optimizer, "supports_update_scaled", False):
        # a presynced handoff (overlap_grad_sync: the bucket wires
        # already ran inside the backward, UNSCALED there) only exists
        # for ZeRO engine optimizers, whose update_scaled takes it
        kw = {} if presynced is None else {"presynced": presynced}
        new_params, new_state, finite = optimizer.update_scaled(
            grads, opt_state, params, scale=scaler_state.loss_scale,
            clip_norm=clip_grad_norm, sumsq_reduce=clip_sumsq,
            finite_sync=lambda f: sync_found_inf(f, sync_axes), **kw,
        )
    else:
        grads, finite = loss_scaler.unscale(scaler_state, grads)
        finite = sync_found_inf(finite, sync_axes)
        new_params, new_state = optimizer.update(
            grads, opt_state, params, grads_finite=finite
        )
    _stat_offer("all_finite", finite)
    new_scaler_state = loss_scaler.update(scaler_state, finite)
    if step_guard is None:
        return new_params, new_state, new_scaler_state
    return (new_params, new_state, new_scaler_state,
            step_guard.update(guard_state, finite))


def _apply_guarded_update(grads, optimizer, opt_state, params, sync_axes,
                          step_guard, guard_state, clip_grad_norm=None,
                          clip_sumsq=None, presynced=None):
    """Unscaled step-guard tail: the amp ``all_finite`` predicate alone
    (no loss scaler) gates the optimizer commit and feeds the guard —
    fp32/bf16 runs get the same survive-a-NaN-step semantics the fp16
    path has always had.  Engine optimizers fold the vote (and the
    optional clip) into the update's grad read (``scale=None`` skips
    the unscale)."""
    from apex_tpu.amp.scaler import all_finite
    from apex_tpu.transformer.amp.grad_scaler import sync_found_inf

    if getattr(optimizer, "supports_update_scaled", False):
        kw = {} if presynced is None else {"presynced": presynced}
        new_params, new_state, finite = optimizer.update_scaled(
            grads, opt_state, params, clip_norm=clip_grad_norm,
            sumsq_reduce=clip_sumsq,
            finite_sync=lambda f: sync_found_inf(f, sync_axes), **kw,
        )
    else:
        finite = sync_found_inf(all_finite(grads), sync_axes)
        new_params, new_state = optimizer.update(
            grads, opt_state, params, grads_finite=finite
        )
    _stat_offer("all_finite", finite)
    return new_params, new_state, step_guard.update(guard_state, finite)


def _telemetry_wrap(fn, n_state, has_scaler, telemetry):
    """Wrap one local-step variant with the StepStats observer: a
    :class:`~apex_tpu.observability.StepStats` pytree rides right after
    the scaler/guard states (before the data), accumulating loss, the
    grad norm the fused clip reduction already computed (captured
    through the trace-time :mod:`~apex_tpu.observability.stepstats`
    seam — never a second read of the grads), the agreed finite vote,
    the loss scale, and the param/update norms.  Stats are observers,
    never participants: the wrapped step's params/loss are the
    UNWRAPPED step's, bitwise (pinned in tests/test_observability.py),
    and the wrapper adds no collectives and no host transfers (pinned
    in tests/test_lowered_invariants.py)."""
    from apex_tpu.observability import stepstats as _st

    def wrapped(params, opt_state, *rest):
        states = rest[:n_state]
        stats, tokens, targets = rest[n_state:]
        with _st.capture() as cap:
            out = fn(params, opt_state, *states, tokens, targets)
        loss = out[-1]
        # with a scaler the NEW scaler state sits right after opt_state
        scale = out[2].loss_scale if has_scaler else None
        new_stats = telemetry.accumulate(
            stats, loss=loss, grad_norm=cap.get("grad_norm"),
            finite=cap.get("all_finite"), loss_scale=scale,
            new_params=out[0], old_params=params)
        return (*out[:-1], new_stats, loss)

    return wrapped


def _step_variant(loss_scaler, step_guard, variants, specs, sspec,
                  data_spec, telemetry=None, wrap=None):
    """Pick the local-step variant and its shard_map specs for a
    scaler×guard(×telemetry) combination.  ``variants`` maps
    (has_scaler, has_guard) to the local step fn; each enabled feature
    adds one replicated scalar-state arg (scaler state, then guard
    state, then the StepStats window) between the optimizer state and
    the data, and one replicated output before the loss.  Returns
    ``(fn, in_specs, out_specs, stats_argnum)`` — ``stats_argnum`` is
    the StepStats position (for donation), or None.  ``wrap``: applied
    to the chosen step last (a family's state, ``make_train_step``)."""
    from jax.sharding import PartitionSpec as P

    fn = variants[(loss_scaler is not None, step_guard is not None)]
    n_state = int(loss_scaler is not None) + int(step_guard is not None)
    stats_argnum = None
    if telemetry is not None:
        fn = _telemetry_wrap(fn, n_state, loss_scaler is not None,
                             telemetry)
        stats_argnum = 2 + n_state
        n_state += 1
    if wrap is not None:    # outermost: the telemetry sees what it wraps
        fn = wrap(fn)
    state_specs = (P(),) * n_state
    in_specs = (specs, sspec, *state_specs, data_spec, data_spec)
    out_specs = (specs, sspec, *state_specs, P())
    return fn, in_specs, out_specs, stats_argnum


def _make_gspmd_train_step(
    config: GPTConfig,
    optimizer,
    mesh,
    tp_axis: str,
    dp_axis,
    opt_state_spec,
    donate_state: bool,
    clip_grad_norm,
    loss_scaler=None,
    step_guard=None,
    telemetry=None,
):
    """The ``spmd="auto"`` half of :func:`make_train_step`: ONE jitted
    step with ``NamedSharding`` annotations on a named mesh and not a
    single explicit collective — XLA's SPMD partitioner places them
    (SNIPPETS [3], the pjit/GSPMD route).  The param/state shardings
    are the SAME ``param_specs`` tree the shard_map builder uses, so a
    mesh reshape is a constructor argument instead of a new step
    builder, and the analyzer's sharding tier (APX206/207/208) can see
    every annotation statically.

    Numerics contract (pinned in tests/test_gpt.py): the loss is
    bitwise-equal fp32 to the shard_map oracle's per step; params track
    it to a few float32 ulps of gradient.  Strict param-bitwise across
    the two is not achievable: the tied embedding's two gradient
    contributions (lookup scatter + head dot) are all-reduced SEPARATELY
    by the partitioner but summed before the one pmean in the
    shard_map program — a summation-association difference no source
    spelling removes.  Everything else (LN param grads included — see
    ``normalization.fused_layer_norm._lead_sum``) associates
    identically."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    for knob, why in (
        (config.moe, "MoE (expert all_to_all is a shard_map program)"),
        (config.sequence_parallel, "sequence parallelism (Megatron SP "
         "is an explicit-collective layout)"),
        (config.use_flash_attention, "flash attention (a pallas_call "
         "is opaque to the SPMD partitioner; use the shard_map path)"),
        (config.fused_ce, "fused CE (the chunked/Pallas heads bypass "
         "the GSPMD-native CE twin)"),
    ):
        if knob:
            raise NotImplementedError(
                f"make_train_step(spmd='auto') does not support {why}")
    if isinstance(dp_axis, (tuple, list)):
        raise NotImplementedError(
            "spmd='auto' with a hierarchical dp split is not wired: "
            "XLA places one flat dp sync; use the shard_map path with "
            "dp_axis=(outer, inner)")
    if hasattr(optimizer, "state_partition_spec"):
        raise NotImplementedError(
            "spmd='auto' with a ZeRO optimizer is not wired (its "
            "per-bucket reduce-scatter is an explicit shard_map "
            "program); use the shard_map path")
    if dp_axis is None:
        raise ValueError("spmd='auto' shards the batch over dp_axis; "
                         "pass a mesh axis name")
    if tp_axis != "tp":
        # param_specs spells the tensor axis literally; renaming it is
        # a spec-tree feature, not a builder knob — reject loudly
        # instead of dying inside NamedSharding construction
        raise NotImplementedError(
            f"spmd='auto' requires tp_axis='tp' (got {tp_axis!r}): "
            "param_specs hard-codes the 'tp' axis name in its "
            "PartitionSpecs")
    if dp_axis not in mesh.axis_names or "tp" not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} must include "
            f"{dp_axis!r} and 'tp' for the spmd='auto' step")
    if clip_grad_norm is not None \
            and not getattr(optimizer, "supports_update_scaled", False):
        raise ValueError(
            "clip_grad_norm needs an engine optimizer (OptimizerBase "
            "subclass) — the clip folds into its fused grad pass")

    # An optimizer's tree state updates a leaf at a time (optimizers/
    # base.py:_dispatch), which is the spelling GSPMD needs: packing
    # differently-sharded leaves into one flat bucket both defeats the
    # sharding (the concat forces all-gathers) and mis-partitions
    # outright — XLA's SPMD pass was observed returning zeroed pack
    # segments for the stacked tp-sharded leaves on the CPU backend
    # (params came back as ``-lr*g``).  ``opt_state_spec`` describes
    # per-leaf slots for that reason: an optimizer's state has no
    # other layout.

    specs = param_specs(config)
    sspec = opt_state_spec
    if sspec is None:
        from apex_tpu.optimizers.fused_adam import AdamState

        sspec = AdamState(step=P(), exp_avg=specs, exp_avg_sq=specs,
                          master=None)

    def shard(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda s: isinstance(s, P))

    pshard = shard(specs)
    sshard = shard(sspec)
    dshard = NamedSharding(mesh, P(dp_axis, None))
    rshard = NamedSharding(mesh, P())

    def grads_of(params, tokens, targets, post_loss):
        loss, grads = jax.value_and_grad(
            lambda p: post_loss(gpt_loss_spmd(p, tokens, targets, config))
        )(params)
        # keep the grads on the param layout: this constraint is what
        # turns the dp batch shard into ONE all-reduce per leaf (the
        # pmean of the shard_map program) instead of a deferred gather
        grads = jax.lax.with_sharding_constraint(grads, pshard)
        return loss, grads

    def local_step(params, opt_state, tokens, targets):
        loss, grads = grads_of(params, tokens, targets, lambda l: l)
        if clip_grad_norm is not None:
            # global arrays: the plain in-optimizer sumsq IS the global
            # norm — no cross-rank sumsq_reduce hook needed
            new_params, new_state = optimizer.update(
                grads, opt_state, params, clip_norm=clip_grad_norm)
        else:
            new_params, new_state = optimizer.update(
                grads, opt_state, params)
        return new_params, new_state, loss

    # scaler/guard variants: global arrays make the finite vote a plain
    # reduction — sync_axes=() turns the shard_map tails' sync_found_inf
    # into the identity, so _apply_*_update serve both builders and the
    # scaler hysteresis / guard accounting cannot drift between them
    def guarded_local_step(params, opt_state, guard_state, tokens, targets):
        loss, grads = grads_of(params, tokens, targets, lambda l: l)
        new_params, new_state, new_guard = _apply_guarded_update(
            grads, optimizer, opt_state, params, (), step_guard,
            guard_state, clip_grad_norm=clip_grad_norm)
        return new_params, new_state, new_guard, loss

    def scaled_local_step(params, opt_state, scaler_state, tokens, targets):
        scaled_loss, grads = grads_of(
            params, tokens, targets,
            lambda l: loss_scaler.scale(scaler_state, l))
        loss = scaled_loss / scaler_state.loss_scale
        new_params, new_state, new_scaler_state = _apply_scaled_update(
            loss_scaler, scaler_state, grads, optimizer, opt_state,
            params, (), clip_grad_norm=clip_grad_norm)
        return new_params, new_state, new_scaler_state, loss

    def guarded_scaled_local_step(params, opt_state, scaler_state,
                                  guard_state, tokens, targets):
        scaled_loss, grads = grads_of(
            params, tokens, targets,
            lambda l: loss_scaler.scale(scaler_state, l))
        loss = scaled_loss / scaler_state.loss_scale
        new_params, new_state, new_scaler_state, new_guard = \
            _apply_scaled_update(
                loss_scaler, scaler_state, grads, optimizer, opt_state,
                params, (), step_guard=step_guard, guard_state=guard_state,
                clip_grad_norm=clip_grad_norm)
        return new_params, new_state, new_scaler_state, new_guard, loss

    fn = {(True, True): guarded_scaled_local_step,
          (True, False): scaled_local_step,
          (False, True): guarded_local_step,
          (False, False): local_step}[
        (loss_scaler is not None, step_guard is not None)]
    n_state = int(loss_scaler is not None) + int(step_guard is not None)
    stats_argnum = None
    if telemetry is not None:
        fn = _telemetry_wrap(fn, n_state, loss_scaler is not None,
                             telemetry)
        stats_argnum = 2 + n_state
        n_state += 1

    donate = (0, 1) if donate_state else ()
    if stats_argnum is not None:
        donate = (*donate, stats_argnum)
    return jax.jit(
        fn,
        in_shardings=(pshard, sshard, *(rshard,) * n_state, dshard, dshard),
        out_shardings=(pshard, sshard, *(rshard,) * n_state, rshard),
        donate_argnums=donate,
    )


def make_train_step(
    config: GPTConfig,
    optimizer,
    mesh,
    tp_axis: str = "tp",
    dp_axis="dp",
    cp_axis: Optional[str] = None,
    opt_state_spec=None,
    loss_scaler=None,
    donate_state: bool = False,
    step_guard=None,
    chaos=None,
    clip_grad_norm=None,
    grad_sync_dtype=None,
    telemetry=None,
    spmd: str = "shard_map",
    overlap_grad_sync: bool = False,
):
    """Build a jitted tp×dp train step over ``mesh``.

    ``overlap_grad_sync``: issue each gradient bucket's sync collective
    INSIDE the backward pass, the moment its cotangents materialize,
    instead of after the whole backward — the backward runs as three
    ``jax.vjp`` segments (head, stacked-layer scan, embedding) and the
    ready buckets' reduce-scatters (ZeRO) or quantized pmeans
    (replicated ``grad_sync_dtype``) are traced between them, so XLA's
    latency-hiding scheduler can run bucket k's collective concurrently
    with the remaining backward dots (the reference's
    ``overlap_grad_sync``/DDP-hook overlap,
    ``distributed_fused_adam.py:2158``).  The segments are the same
    functions the monolithic forward composes, so fp32 loss/params are
    BITWISE identical to the unoverlapped build (pinned in
    tests/test_distributed_optimizers.py); only collective placement
    moves.  Requires a dp grad sync to overlap (a ZeRO optimizer or
    ``grad_sync_dtype``); not wired for MoE, sequence parallelism, cp,
    or ``spmd='auto'``.

    ``spmd``: ``"shard_map"`` (default) builds the explicit-collective
    Megatron program documented below.  ``"auto"`` builds the
    GSPMD-native step instead — plain ``jit`` with ``NamedSharding``
    annotations from the same ``param_specs`` tree and ZERO explicit
    collectives; XLA's SPMD partitioner places them, so new mesh
    shapes need no new step code.  The auto path supports
    ``opt_state_spec``/``donate_state``/``clip_grad_norm`` and — since
    the finite vote needs no collectives on global arrays — the full
    ``loss_scaler``/``step_guard``/``telemetry`` tails; it rejects the
    explicitly-collective features loudly (ZeRO, hierarchical dp, cp,
    MoE, SP, flash/fused-CE kernels, chaos, grad_sync_dtype,
    overlap_grad_sync — see docs/parallelism.md for the migration
    map).  Its loss is
    bitwise-equal fp32 to this builder's per step on the same mesh
    (pinned in tests/test_gpt.py), and its lowering is pinned through
    ``analysis.lowered.assert_sharding``/``assert_spmd_collectives``.

    ``dp_axis``: one mesh axis name (flat data parallelism), ``None``,
    or the HIERARCHICAL ``(outer, inner)`` pair — the dp world split
    over a slow cross-slice axis and a fast intra-slice axis (a pod's
    DCN x ICI topology).  With the pair, the batch shards over both
    axes, the loss pmean runs over the pair, a ZeRO optimizer must be
    constructed with the same ``dp_axes=`` (its two-hop reduce-scatter
    owns the grad sync — cross-slice traffic drops to ``1/dp_inner``),
    and the replicated ``grad_sync_dtype`` knob quantizes the two-hop
    pmean (:mod:`apex_tpu.contrib.optimizers._hierarchical_sync`).

    ``telemetry``: a :class:`apex_tpu.observability.StepTelemetry` — a
    :class:`~apex_tpu.observability.StepStats` window rides the step
    right after the guard state (or scaler state, or in their place):
    ``step(params, opt_state, [scaler], [guard], stats, tokens,
    targets) -> (..., stats, loss)``.  Loss, the global grad norm
    (REUSED from the fused clip reduction — rank-local when
    ``clip_grad_norm`` is off), the finite vote, the loss scale, and
    param/update norms accumulate device-side; fetch the window
    asynchronously with :class:`~apex_tpu.observability.AsyncFetcher`
    and swap in ``telemetry.init()`` — the stats buffers are ALWAYS
    donated (rebind every call).  Telemetry adds zero collectives,
    zero host transfers, and leaves loss/params bitwise identical.

    ``grad_sync_dtype``: quantize the REPLICATED data-parallel
    gradient sync (``int8``/``float8_e4m3fn``/``float8_e5m2``): the dp
    pmean becomes a reduce-scatter + all-gather pair on the wire dtype
    with shared per-block fp32 scales
    (:func:`apex_tpu.contrib.optimizers._quantized_sync
    .quantized_pmean`).  STATELESS — the replicated step has no
    optimizer-state channel, so there is no error-feedback residual
    here; for compressed sync with feedback use a ZeRO optimizer with
    its own ``grad_sync_dtype`` (which owns the dp sync and must not
    also be quantized here — pass the knob to exactly one of the two).

    ``clip_grad_norm``: global-l2 gradient clipping (torch
    ``clip_grad_norm_`` semantics) folded into the optimizer's fused
    grad pass — with an engine optimizer the unscale, the clip norm,
    the finite vote, and the update math share one read of the grads
    instead of four sweeps.  Requires an
    :class:`apex_tpu.optimizers.base.OptimizerBase` optimizer.

    ``opt_state_spec``: PartitionSpec tree for the optimizer state; by
    default the FusedAdam state shape is assumed (m/v mirror the param
    sharding, scalars replicated) and ZeRO optimizers supply their own —
    pass this for other state shapes (e.g. ``SGDState``).

    ``donate_state``: donate the params and optimizer-state buffers to
    the step (``jax.jit`` ``donate_argnums``) — XLA otherwise holds
    input AND output copies (~3x param bytes with Adam) across the
    step.  The caller must rebind both on every call and never touch
    the previous values (the examples do; oracle tests that reuse
    params after stepping must not set this).

    ``loss_scaler``: an :class:`apex_tpu.amp.DynamicLossScaler` /
    ``StaticLossScaler`` — the flagship fp16 path (reference
    ``apex/amp/handle.py:16`` scale_loss × DDP composition).  Backward
    runs on the SCALED loss so half-precision cotangents don't
    underflow; grads are unscaled in fp32, the finite flag is agreed
    across every model-parallel axis (the TP-aware GradScaler semantics,
    reference ``apex/transformer/amp/grad_scaler.py:21-126``), the
    optimizer step is predicated on it, and the scaler state updates
    device-side.  The step then takes/returns a scaler state:
    ``step(params, opt_state, scaler_state, tokens, targets) ->
    (params, opt_state, scaler_state, loss)``.

    ``step_guard``: an :class:`apex_tpu.resilience.StepGuard` — a
    :class:`~apex_tpu.resilience.step_guard.GuardState` rides the step
    right after the scaler state (or in its place without a scaler):
    non-finite steps are skipped device-side (the existing predicated
    update) AND counted, so the loop can enforce a consecutive-bad-step
    abort budget with ``guard.check`` at its own sync cadence.  Without
    a scaler the guard brings its own ``all_finite`` vote, agreed over
    the same model-parallel axes.

    ``chaos``: an armed :class:`apex_tpu.resilience.ChaosMonkey` whose
    planned NaN-grad steps are baked (as constants) into the compiled
    step — the loss is multiplied by the plan's 1.0/NaN scalar at the
    guard's step counter, poisoning every gradient of exactly the
    planned steps with zero per-step host work.  Requires
    ``step_guard`` (the counter lives in its state).

    The TPU shape of reference §3.2's iteration: value_and_grad inside
    ``shard_map`` (TP collectives via the mappings), gradient ``pmean``
    over ``dp`` (the DDP allreduce, ``apex/parallel/distributed.py:429``),
    then the fused optimizer update on local shards.
    Without a scaler, returns
    ``step(params, opt_state, tokens, targets) -> (params, opt_state, loss)``.

    **A second family.**  ``config`` may be another family's
    (``config.train_family()``, as the scheduler asks a config for its
    ``served_model()``; today :class:`apex_tpu.models.afmoe.AFMoEConfig`):
    the family gives the loss with its auxiliary outputs, the parameter
    specs, which leaves are STATE that no optimizer touches (a router's
    choice-only bias, device-side counters) and how a step moves them,
    and which leaves are experts.  The optimizer's tree is then
    ``family.split(params)[0]`` (init the optimizer on that); the step
    differentiates and updates it alone, then applies
    ``family.update_state`` to the rest from the loss's auxiliary
    outputs (summed over the data axis), AFTER the optimizer.  Optimizer
    application, donation, scaler, guard and telemetry are the tails
    below, unchanged; what a family does not have yet (tensor and
    context parallelism, ZeRO over its experts, quantized or overlapped
    sync, ``spmd="auto"``) raises.
    """
    if spmd not in ("shard_map", "auto"):
        raise ValueError(f"spmd must be 'shard_map' or 'auto', got {spmd!r}")
    family = (config.train_family() if hasattr(config, "train_family")
              else None)
    if family is not None:
        for bad, why in (
            (spmd == "auto", "spmd='auto'"),
            (mesh.shape.get(tp_axis, 1) > 1, "tensor parallelism"),
            (cp_axis is not None, "context parallelism"),
            (isinstance(dp_axis, (tuple, list)), "hierarchical dp"),
            (hasattr(optimizer, "state_partition_spec"),
             "a ZeRO optimizer (its experts' leaves are not dp wires)"),
            (grad_sync_dtype is not None, "grad_sync_dtype"),
            (overlap_grad_sync, "overlap_grad_sync"),
        ):
            if bad:
                raise NotImplementedError(
                    f"make_train_step for {type(config).__name__} does "
                    f"not take {why} yet")
    # GPT's own switches; another family has neither
    gpt_moe = bool(getattr(config, "moe", False))
    sequence_parallel = bool(getattr(config, "sequence_parallel", False))
    if spmd == "auto":
        for arg, name in ((cp_axis, "cp_axis"), (chaos, "chaos"),
                          (grad_sync_dtype, "grad_sync_dtype")):
            if arg is not None:
                raise NotImplementedError(
                    f"make_train_step(spmd='auto') does not take {name} "
                    "yet; use the shard_map path (the GSPMD step is the "
                    "parity-pinned core, features migrate per "
                    "docs/parallelism.md)")
        if overlap_grad_sync:
            raise NotImplementedError(
                "make_train_step(spmd='auto') does not take "
                "overlap_grad_sync: the GSPMD path has no explicit "
                "collectives to reorder (XLA already schedules its "
                "grad all-reduces against the backward); the knob "
                "belongs to the shard_map path")
        return _make_gspmd_train_step(
            config, optimizer, mesh, tp_axis, dp_axis, opt_state_spec,
            donate_state, clip_grad_norm, loss_scaler=loss_scaler,
            step_guard=step_guard, telemetry=telemetry)

    from jax.sharding import PartitionSpec as P

    # hierarchical data parallelism: dp_axis=(outer, inner) splits the
    # dp world over two mesh axes (slow cross-slice x fast intra-slice)
    # — the loss pmean runs over the pair, a ZeRO optimizer must carry
    # the same dp_axes (its two-hop reduce-scatter owns the sync), and
    # the replicated quantized knob routes through the two-hop pmean
    dp_hier = isinstance(dp_axis, (tuple, list))
    if dp_hier:
        dp_axis = tuple(dp_axis)
        if len(dp_axis) not in (2, 3):
            raise ValueError(
                f"a hierarchical dp_axis is the (outer, inner) pair — or "
                f"the (dcn, outer, inner) triple — of mesh axes ordered "
                f"slow to fast, got {dp_axis!r}")
        if gpt_moe:
            raise NotImplementedError(
                "MoE expert parallelism over a hierarchical dp split is "
                "not wired (EP rides a single dp axis)")

    ep_axis = dp_axis if gpt_moe else None  # EP rides DP
    if ep_axis is not None:
        ep = mesh.shape[ep_axis]
        if config.moe_num_experts % ep != 0:
            raise ValueError(
                f"moe_num_experts ({config.moe_num_experts}) must be divisible "
                f"by the '{ep_axis}' mesh axis size ({ep}): experts shard over "
                "dp (EP rides DP)"
            )
    specs = (param_specs(config, ep_axis=ep_axis) if family is None
             else family.param_specs())
    #: a family step's state and the loss's auxiliary outputs, within
    #: one trace of the step: set by ``with_family_state`` below, read
    #: and filled by ``value_and_grads``
    cell = {}

    qspec = None
    if grad_sync_dtype is not None:
        from apex_tpu.contrib.optimizers import _quantized_sync

        qspec = _quantized_sync.qspec_of(grad_sync_dtype)
        if qspec is None:
            raise ValueError(
                f"grad_sync_dtype={jnp.dtype(grad_sync_dtype).name!r}: the "
                "step builder's knob quantizes the replicated dp sync and "
                "accepts int8/float8_e4m3fn/float8_e5m2 only (wide sync "
                "dtypes belong to the ZeRO optimizer's own knob)")
        if hasattr(optimizer, "state_partition_spec"):
            raise ValueError(
                "a ZeRO optimizer owns the dp grad sync: pass "
                "grad_sync_dtype to its constructor (where it gains the "
                "error-feedback residual), not to make_train_step")
        if gpt_moe:
            raise NotImplementedError(
                "quantized dp sync + MoE is not wired: expert grads are "
                "dp-sharded sums, not pmean'd")
        if dp_axis is None:
            raise ValueError("grad_sync_dtype quantizes the dp sync; "
                             "this step has dp_axis=None")

    def pmean_grads(grads, ax, skip_experts):
        """pmean over a data axis.  Expert grads are dp-SHARDED, not
        replicated: the all_to_all transpose already delivered every
        rank's cotangents (a sum over dp), so the mean-loss gradient is
        that sum divided by dp — never pmean'd (which would mix grads of
        *different* experts)."""
        if qspec is not None and ax == dp_axis:
            from apex_tpu.contrib.optimizers import _quantized_sync

            if dp_hier:
                from apex_tpu.contrib.optimizers import _hierarchical_sync

                # multi-hop quantized all-reduce: scatter fast to slow,
                # mirrored gathers, every payload hop at the wire
                # dtype — each slower hop carries 1/prod(faster sizes)
                plan = _hierarchical_sync.hierarchical_plan(
                    dp_axis, {a: mesh.shape[a] for a in dp_axis},
                    grad_wire_dtype=grad_sync_dtype)
                return _hierarchical_sync.quantized_multi_hop_pmean(
                    grads, plan, qspec)
            # quantized all-reduce: reduce-scatter + all-gather, both
            # on the wire dtype (the same scale machinery as ZeRO's
            # compressed sync, minus the residual — no state channel)
            return _quantized_sync.quantized_pmean(
                grads, ax, qspec, world=mesh.shape[dp_axis])
        if not (skip_experts and gpt_moe):
            return jax.tree.map(lambda g: jax.lax.pmean(g, ax), grads)
        from apex_tpu.transformer.expert_parallel import EXPERT_PARAM_KEYS

        inv = 1.0 / jax.lax.axis_size(ax)
        moe = grads["layers"]["moe"]
        rest = {**grads, "layers": {k: v for k, v in grads["layers"].items() if k != "moe"}}
        rest = jax.tree.map(lambda g: jax.lax.pmean(g, ax), rest)
        moe = {
            k: (v * inv if k in EXPERT_PARAM_KEYS else jax.lax.pmean(v, ax))
            for k, v in moe.items()
        }
        rest["layers"]["moe"] = moe
        return rest

    # A ZeRO optimizer (state_partition_spec present) owns the dp grad
    # sync via its per-bucket reduce-scatter; grads then stay local
    # over dp and the collectives live inside the optimizer.
    zero_opt = hasattr(optimizer, "state_partition_spec")
    if zero_opt and gpt_moe:
        raise NotImplementedError(
            "ZeRO + MoE expert sharding both claim the dp axis; not wired"
        )
    _check_zero_axis(zero_opt, optimizer, dp_axis)

    if overlap_grad_sync:
        for bad, why in (
            (gpt_moe, "MoE (expert grads are dp-sharded sums, not "
             "bucketed pmean wires)"),
            (sequence_parallel, "sequence parallelism "
             "(sp_grad_sync is a whole-tree pass after the backward)"),
            (cp_axis is not None, "context parallelism (cp grads need "
             "a second pmean after the backward)"),
        ):
            if bad:
                raise NotImplementedError(
                    f"overlap_grad_sync is not wired for {why}")
        if dp_axis is None:
            raise ValueError("overlap_grad_sync overlaps the dp grad "
                             "sync; this step has dp_axis=None")
        if not zero_opt and qspec is None:
            raise ValueError(
                "overlap_grad_sync needs a per-bucket dp grad sync to "
                "overlap — a ZeRO optimizer (each bucket's "
                "reduce-scatter issues as its grads materialize) or "
                "grad_sync_dtype= (per-bucket quantized pmean); the "
                "plain replicated pmean is one whole-tree sweep with "
                "nothing to interleave")

    def sync_loss_and_grads(loss, grads):
        """cp behaves as a data axis for grads: each rank differentiated
        its local-chunk loss (ring-travelled k/v cotangents included),
        so pmean over cp (and dp) recovers the global-mean-loss grads.
        With ``overlap_grad_sync`` the dp sync already happened inside
        the backward (per bucket), so only the loss pmean remains."""
        if sequence_parallel:
            grads = sp_grad_sync(grads, tp_axis)
        for ax in (cp_axis, dp_axis):
            if ax is not None:
                loss = jax.lax.pmean(loss, ax)
                if ax == dp_axis and (zero_opt or overlap_grad_sync):
                    continue
                grads = pmean_grads(grads, ax, skip_experts=(ax == dp_axis))
        return loss, grads

    def overlap_value_and_grads(params, tokens, targets, post_loss,
                                residuals, scale):
        """The backward-overlapped twin of ``value_and_grad(loss_fn)``:
        the forward runs as the three ``_*_segment`` functions, each
        under its own ``jax.vjp``, and the backward is their cotangent
        chain — after each segment's backward, every bucket whose
        leaves all have cotangents is packed and its sync collective
        traced IMMEDIATELY, before the next (earlier) segment's
        backward.  Gradient readiness on the scan-stacked model has
        exactly three stages: final-LN leaves after the head backward,
        every ``layers.*`` leaf after the scan backward, and the (tied)
        embedding + positions after the embed backward.

        Returns ``(scaled_loss, grads, presynced)``: with a ZeRO
        optimizer ``grads`` is None and ``presynced`` the per-bucket
        ``(shards, residuals, wires)`` handoff its ``update*`` consumes
        in place of the grad tree; on the replicated quantized path
        ``grads`` is the dp-SYNCED (still loss-scaled) grad tree and
        ``presynced`` None.  Every per-bucket operation is the same
        function the unoverlapped build calls on the same values, so
        the arithmetic is bitwise identical — only collective placement
        in the trace moves."""
        from apex_tpu.optimizers import bucketing

        t = targets.transpose(1, 0)  # (S, B)

        def seg_embed(embed_w, pos_w):
            return _embed_segment(embed_w, pos_w, tokens, config, tp_axis,
                                  cp_axis)

        def seg_layers(layers_p, x):
            return _layers_segment(layers_p, x, config, tp_axis, cp_axis,
                                   ep_axis)

        def seg_head(ln_scale, ln_bias, embed_w, x):
            h = _head_segment(x, ln_scale, ln_bias, config, tp_axis)
            return jnp.mean(lm_head_loss(h, embed_w, t, config, tp_axis))

        unknown = sorted(set(params) - set(_OVERLAP_STAGES))
        if unknown:
            raise NotImplementedError(
                f"overlap_grad_sync does not know the gradient-readiness "
                f"stage of param group(s) {unknown}")

        x0, vjp_embed = jax.vjp(seg_embed, params["embed"],
                                params.get("pos_embed"))
        (x1, ys), vjp_layers = jax.vjp(seg_layers, params["layers"], x0)
        loss, vjp_head = jax.vjp(seg_head, params["final_ln_scale"],
                                 params["final_ln_bias"], params["embed"],
                                 x1)
        scaled_loss, vjp_post = jax.vjp(post_loss, loss)

        leaves, treedef = jax.tree.flatten(params)
        idx_tree = jax.tree.unflatten(treedef, list(range(len(leaves))))
        stages = [0] * len(leaves)
        for key, sub in idx_tree.items():
            for li in jax.tree.leaves(sub):
                stages[li] = _OVERLAP_STAGES[key]
        cot = [None] * len(leaves)

        def fill(key, val):
            for li, v in zip(jax.tree.leaves(idx_tree[key]),
                             jax.tree.leaves(val)):
                cot[li] = v

        if zero_opt:
            plan = optimizer._plan_of_local(params)
            by_stage = bucketing.buckets_by_stage(plan, stages, 3)
            n = len(plan.buckets)
            g_shards, res_new, wires = [None] * n, [None] * n, [None] * n

            def wire(stage):
                for bi in by_stage[stage]:
                    res = residuals[bi] if optimizer._quantized else None
                    g_shards[bi], res_new[bi], wires[bi] = \
                        optimizer.bucket_grad_wire(
                            plan.buckets[bi], cot, scale=scale,
                            residual=res)
        else:
            # replicated quantized pmean, one bucket at a time — the
            # grads stay SCALED on the wire exactly as on the
            # unoverlapped path (the downstream update tail unscales)
            from apex_tpu.contrib.optimizers import _quantized_sync

            if dp_hier:
                from apex_tpu.contrib.optimizers import _hierarchical_sync

                hplan = _hierarchical_sync.hierarchical_plan(
                    dp_axis, {a: mesh.shape[a] for a in dp_axis},
                    grad_wire_dtype=grad_sync_dtype)
                world = 1
                for s in hplan.traced_sizes():
                    world = world * s
            else:
                hplan, world = None, mesh.shape[dp_axis]
            plan = bucketing.plan_of(params, shard_pad=world)
            by_stage = bucketing.buckets_by_stage(plan, stages, 3)
            synced = [None] * len(plan.buckets)

            def wire(stage):
                for bi in by_stage[stage]:
                    h = bucketing.pack_bucket(plan.buckets[bi], cot,
                                              jnp.float32)
                    if hplan is not None:
                        synced[bi] = (_hierarchical_sync
                                      .quantized_multi_hop_pmean_bucket(
                                          h, hplan, qspec))
                    else:
                        synced[bi] = _quantized_sync.quantized_pmean_bucket(
                            h, dp_axis, qspec, world)

        (seed,) = vjp_post(jnp.ones_like(scaled_loss))
        d_ln_scale, d_ln_bias, d_embed_head, d_x1 = vjp_head(seed)
        fill("final_ln_scale", d_ln_scale)
        fill("final_ln_bias", d_ln_bias)
        wire(0)
        d_layers, d_x0 = vjp_layers((d_x1, jax.tree.map(jnp.zeros_like,
                                                        ys)))
        fill("layers", d_layers)
        wire(1)
        d_embed_lookup, d_pos = vjp_embed(d_x0)
        fill("embed", d_embed_head + d_embed_lookup)
        if "pos_embed" in params:
            fill("pos_embed", d_pos)
        wire(2)

        if zero_opt:
            return scaled_loss, None, (tuple(g_shards), tuple(res_new),
                                       tuple(wires))
        return scaled_loss, bucketing.unpack(plan, synced), None

    def value_and_grads(params, opt_state, tokens, targets, post_loss,
                        scale=None):
        """The one grads seam all four step variants share:
        ``(scaled_loss, grads, presynced)``.  Monolithic
        ``value_and_grad`` with ``presynced=None`` normally; the
        segmented overlapped backward when ``overlap_grad_sync``."""
        if family is not None:
            def family_loss_fn(p):
                loss, aux = family.loss(family.merge(p, cell["state"]),
                                        tokens, targets)
                return post_loss(loss), aux

            (scaled_loss, cell["aux"]), grads = jax.value_and_grad(
                family_loss_fn, has_aux=True)(params)
            return scaled_loss, grads, None
        if not overlap_grad_sync:
            def loss_fn(p):
                return post_loss(gpt_loss(p, tokens, targets, config,
                                          tp_axis, cp_axis, ep_axis))

            scaled_loss, grads = jax.value_and_grad(loss_fn)(params)
            return scaled_loss, grads, None
        return overlap_value_and_grads(
            params, tokens, targets, post_loss,
            getattr(opt_state, "residual", ()), scale)

    if chaos is not None and step_guard is None:
        raise ValueError("chaos NaN injection needs step_guard (the "
                         "injection step counter lives in GuardState)")

    wedge_axis = ((dp_axis[0] if dp_hier else dp_axis)
                  if dp_axis is not None else tp_axis)

    def chaos_wedge(loss, guard_step):
        """Chaos "wedge one rank's collective site": on the planned
        (rank, step) an ``io_callback`` stalls exactly that rank right
        before the loss/grad sync, so its PEERS block device-side in
        the collective waiting for it — the truthful presentation of a
        wedged all-reduce, which only the host-side step watchdog
        (:class:`apex_tpu.resilience.StepWatchdog`) can notice.  The
        callback's token is folded into the loss to order it before
        the sync; off-plan (rank, step) pairs return immediately."""
        if chaos is None or not getattr(chaos, "wedges_collective", False):
            return loss
        from jax.experimental import io_callback

        def host(s, r):
            chaos.collective_wedge_callback(s, r)
            return np.float32(0.0)

        rank = jax.lax.axis_index(wedge_axis)
        tok = io_callback(host, jax.ShapeDtypeStruct((), jnp.float32),
                          guard_step, rank)
        return loss + tok

    # the clip's global norm must agree across ranks: sharded leaves'
    # Σx² psum over exactly their spec axes, replicated leaves don't
    clip_reduce = _clip_reduce_for(optimizer, clip_grad_norm, specs)

    # tp-sharded grad shards can overflow on one rank only; with
    # ZeRO (local dp grads) or MoE (dp-sharded expert grads) the dp
    # ranks can disagree too — every such axis must join the vote
    # (pmean'd axes already agree: a nan poisons every rank's copy)
    sync_axes = [tp_axis]
    if (zero_opt or gpt_moe) and dp_axis is not None:
        sync_axes.extend(dp_axis if dp_hier else (dp_axis,))

    def local_step(params, opt_state, tokens, targets):
        loss, grads, presynced = value_and_grads(
            params, opt_state, tokens, targets, lambda l: l)
        loss, grads = sync_loss_and_grads(loss, grads)
        kw = {} if presynced is None else {"presynced": presynced}
        if clip_grad_norm is not None:
            new_params, new_state = optimizer.update(
                grads, opt_state, params, clip_norm=clip_grad_norm,
                sumsq_reduce=clip_reduce, **kw)
        else:
            new_params, new_state = optimizer.update(grads, opt_state,
                                                     params, **kw)
        return new_params, new_state, loss

    def guarded_local_step(params, opt_state, guard_state, tokens, targets):
        fault = chaos.grad_fault(guard_state.step) if chaos is not None else None

        def post_loss(l):
            return l * fault if fault is not None else l

        loss, grads, presynced = value_and_grads(
            params, opt_state, tokens, targets, post_loss)
        loss = chaos_wedge(loss, guard_state.step)
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_guard = _apply_guarded_update(
            grads, optimizer, opt_state, params, sync_axes,
            step_guard, guard_state, clip_grad_norm=clip_grad_norm,
            clip_sumsq=clip_reduce, presynced=presynced,
        )
        return new_params, new_state, new_guard, loss

    def scaled_local_step(params, opt_state, scaler_state, tokens, targets):
        def post_loss(l):
            return loss_scaler.scale(scaler_state, l)

        scaled_loss, grads, presynced = value_and_grads(
            params, opt_state, tokens, targets, post_loss,
            scale=scaler_state.loss_scale)
        loss = scaled_loss / scaler_state.loss_scale
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_scaler_state = _apply_scaled_update(
            loss_scaler, scaler_state, grads, optimizer, opt_state, params,
            sync_axes, clip_grad_norm=clip_grad_norm,
            clip_sumsq=clip_reduce, presynced=presynced,
        )
        return new_params, new_state, new_scaler_state, loss

    def guarded_scaled_local_step(params, opt_state, scaler_state,
                                  guard_state, tokens, targets):
        fault = chaos.grad_fault(guard_state.step) if chaos is not None else None

        def post_loss(l):
            if fault is not None:
                l = l * fault
            return loss_scaler.scale(scaler_state, l)

        scaled_loss, grads, presynced = value_and_grads(
            params, opt_state, tokens, targets, post_loss,
            scale=scaler_state.loss_scale)
        loss = scaled_loss / scaler_state.loss_scale
        loss = chaos_wedge(loss, guard_state.step)
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_scaler_state, new_guard = \
            _apply_scaled_update(
                loss_scaler, scaler_state, grads, optimizer, opt_state,
                params, sync_axes,
                step_guard=step_guard, guard_state=guard_state,
                clip_grad_norm=clip_grad_norm, clip_sumsq=clip_reduce,
                presynced=presynced,
            )
        return new_params, new_state, new_scaler_state, new_guard, loss

    # optimizer state mirrors param sharding for m/v/master; scalars replicated
    def state_spec_of(params_spec):
        from apex_tpu.optimizers.fused_adam import AdamState

        return AdamState(step=P(), exp_avg=params_spec, exp_avg_sq=params_spec, master=None)

    def with_family_state(local):
        """A local step over the family's whole tree: the optimizer's
        part goes through ``local`` (any of the variants, telemetry
        included), the state is moved after it from the loss's
        auxiliary outputs, summed over the data axis."""
        def stepped(params, opt_state, *rest):
            trainable, cell["state"] = family.split(params)
            out = local(trainable, opt_state, *rest)
            aux, state = cell.pop("aux"), cell.pop("state")
            if dp_axis is not None:
                aux = jax.tree.map(lambda a: jax.lax.psum(a, dp_axis), aux)
            return (family.merge(out[0], family.update_state(state, aux)),
                    *out[1:])

        return stepped

    if opt_state_spec is not None:
        sspec = opt_state_spec
    elif zero_opt:
        sspec = optimizer.state_partition_spec()
    else:
        sspec = state_spec_of(specs if family is None
                              else family.split(specs)[0])
    data_spec = P(dp_axis, cp_axis)  # batch over dp, sequence over cp

    donate = (0, 1) if donate_state else ()
    fn, in_specs, out_specs, stats_argnum = _step_variant(
        loss_scaler, step_guard,
        {(True, True): guarded_scaled_local_step,
         (True, False): scaled_local_step,
         (False, True): guarded_local_step,
         (False, False): local_step},
        specs, sspec, data_spec, telemetry=telemetry,
        wrap=None if family is None else with_family_state)
    if stats_argnum is not None:
        # the StepStats window is always rebound (fetch swaps in fresh
        # zeros), so its tiny buffers always donate
        donate = (*donate, stats_argnum)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=donate)


def params_to_vpp_layout(params, pp: int, vpp: int):
    """Permute layer-stacked params from execution order to the
    stage-major layout the interleaved schedule shards.

    Execution order is virtual-stage-major: global block ``j = v·pp + s``
    (reference fwd_bwd_pipelining_with_interleaving.py:27 assigns stage s
    chunks s, s+pp, ...).  Sharding ``P("pp")`` slices axis 0 into
    contiguous per-stage blocks, so stage s's slice must hold its vpp
    chunks back to back: ``out[(s·vpp + v)·lpc + i] = in[(v·pp + s)·lpc + i]``.
    Train in this layout (element-wise optimizers are layout-blind);
    invert with :func:`params_from_vpp_layout` for canonical checkpoints.
    """
    def perm(a):
        L = a.shape[0]
        lpc = L // (pp * vpp)
        return (
            a.reshape(vpp, pp, lpc, *a.shape[1:])
            .transpose(1, 0, *range(2, a.ndim + 2))
            .reshape(a.shape)
        )

    out = dict(params)
    out["layers"] = jax.tree.map(perm, params["layers"])
    return out


def params_from_vpp_layout(params, pp: int, vpp: int):
    """Inverse of :func:`params_to_vpp_layout`."""
    def unperm(a):
        L = a.shape[0]
        lpc = L // (pp * vpp)
        return (
            a.reshape(pp, vpp, lpc, *a.shape[1:])
            .transpose(1, 0, *range(2, a.ndim + 2))
            .reshape(a.shape)
        )

    out = dict(params)
    out["layers"] = jax.tree.map(unperm, params["layers"])
    return out


def make_pp_train_step(
    config: GPTConfig,
    optimizer,
    mesh,
    num_microbatches: int,
    tp_axis: str = "tp",
    pp_axis: str = "pp",
    dp_axis: Optional[str] = "dp",
    virtual_pipeline_size: int = 1,
    opt_state_spec=None,
    cp_axis: Optional[str] = None,
    loss_scaler=None,
    donate_state: bool = False,
    step_guard=None,
    chaos=None,
    clip_grad_norm=None,
    telemetry=None,
):
    """3D-parallel (tp × pp × dp) train step via the pipeline schedule.

    ``telemetry``: same contract as :func:`make_train_step` — a
    :class:`~apex_tpu.observability.StepStats` window rides after the
    scaler/guard states, accumulated device-side, always donated,
    never a participant in the update.

    ``clip_grad_norm``: global-l2 grad clip folded into the engine
    optimizer's fused grad pass (see :func:`make_train_step`).

    ``opt_state_spec`` overrides the optimizer-state PartitionSpec tree
    (default: FusedAdam state shape; ZeRO optimizers supply their own).

    ``loss_scaler``: fp16 dynamic loss scaling through the pipeline
    (see :func:`make_train_step`): the schedule's backward seed is the
    SCALED loss, found_inf is pmax-agreed over tp AND pp (every stage
    must skip together — the reference's model-parallel GradScaler,
    ``apex/transformer/amp/grad_scaler.py:21-126``), and the step
    signature grows a scaler state:
    ``step(params, opt_state, scaler_state, tokens, targets)``.

    ``cp_axis``: context parallelism inside every stage — the sequence
    shards over the axis and each layer's attention is ring attention
    (4D tp × pp × dp × cp).  All stages run the ring's ppermutes in
    lockstep per tick, so the collectives stay consistent.

    Layer-stacked params shard over ``pp`` on their leading axis and over
    ``tp`` on their weight axes (the layout of reference §3.4: each
    pipeline stage owns L/pp layers, each TP rank a weight shard).  The
    batch splits into ``num_microbatches`` microbatches driven through
    the 1F1B schedule, or the interleaved schedule when
    ``virtual_pipeline_size > 1`` — in that case ``params["layers"]``
    (and the matching optimizer state) must be in the stage-major vpp
    layout from :func:`params_to_vpp_layout`.

    ``step_guard``/``chaos``: same contract as :func:`make_train_step`
    — a guard state rides after the scaler state (or in its place),
    the skip vote is pmax-agreed over tp AND pp (every stage skips
    together), and chaos NaN injection scales the schedule's backward
    seed so the poisoned step is skipped pipeline-wide.
    Returns ``step(params, opt_state, tokens, targets) -> (params,
    opt_state, loss)`` (jitted).
    """
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_with_interleaving,
        forward_backward_pipelining_without_interleaving,
    )

    if isinstance(dp_axis, (tuple, list)):
        raise NotImplementedError(
            "hierarchical dp (dp_axis=(outer, inner)) is wired into "
            "make_train_step only; the pipeline step's dp sync is flat")

    # MoE composes: experts shard over dp (EP rides DP) inside each
    # pipeline stage; every (dp, pp, tp) rank executes the tick program
    # in lockstep, so the per-layer all_to_all stays collective-safe.
    ep_axis = dp_axis if config.moe else None
    if config.moe and dp_axis is None:
        raise ValueError("MoE in the pipeline step needs a dp axis (EP rides DP)")
    if cp_axis is not None and config.sequence_parallel:
        raise ValueError("sequence_parallel (tp) and context parallelism both "
                         "shard the sequence; enable one")
    H = config.hidden_size
    tp = mesh.shape[tp_axis]
    n_local_heads = config.num_attention_heads // tp
    sp = config.sequence_parallel
    vpp = virtual_pipeline_size
    if vpp > 1:
        if config.num_layers % (mesh.shape[pp_axis] * vpp) != 0:
            raise ValueError(
                f"num_layers ({config.num_layers}) must divide into "
                f"pp ({mesh.shape[pp_axis]}) x vpp ({vpp}) chunks"
            )
        if num_microbatches % mesh.shape[pp_axis] != 0:
            # the interleaved slot decode pads M up to a multiple of pp and
            # masks the padding — every padding slot still costs a full
            # tick, so reject rather than silently burn pipeline throughput
            # (the reference's interleaved schedule has the same constraint)
            raise ValueError(
                f"num_microbatches ({num_microbatches}) must be a multiple of "
                f"pp ({mesh.shape[pp_axis]}) when virtual_pipeline_size > 1"
            )

    base = param_specs(config, ep_axis=ep_axis)

    def pp_spec(spec):
        # prepend pp sharding on the stacked-layer axis
        return P(pp_axis, *spec[1:])

    specs = dict(base)
    specs["layers"] = jax.tree.map(
        pp_spec, base["layers"], is_leaf=lambda s: isinstance(s, P)
    )
    # stage-stacked leaves are pp-sharded (their spec leads with pp), so
    # the clip's global norm psums their Σx² over pp (+tp for sharded
    # weights); replicated embeds/norms stay local — the reduce reads
    # all of that off the specs
    clip_reduce = _clip_reduce_for(optimizer, clip_grad_norm, specs)

    def pre_fn(shared, mb):
        tokens = mb["tokens"]
        B, S = tokens.shape
        emb = vocab_parallel_embedding(tokens, shared["embed"], axis_name=tp_axis)
        x = _add_pos_embed(emb.transpose(1, 0, 2), shared.get("pos_embed"),
                           config, cp_axis)
        x = x.astype(config.compute_dtype)
        if sp:
            from apex_tpu.transformer.tensor_parallel.mappings import (
                scatter_to_sequence_parallel_region,
            )

            x = scatter_to_sequence_parallel_region(x, tp_axis)
        return x

    def stage_fn(stage_params, x):
        layer = partial(_layer, config=config, axis_name=tp_axis,
                        n_local_heads=n_local_heads, ep_axis=ep_axis,
                        cp_axis=cp_axis)
        if config.checkpoint_layers:
            layer = remat_layer(layer, config.remat_policy)
        out, aux = jax.lax.scan(lambda c, lp: layer(c, lp), x, stage_params)
        if config.moe:
            # pre-weight the load-balancing aux; the schedule adds it to
            # the loss per (stage, microbatch) unit and seeds its vjp
            return out, config.moe_aux_coef * jnp.sum(aux)
        return out

    def post_fn(shared, x, mb):
        if sp:
            from apex_tpu.transformer.tensor_parallel.mappings import (
                gather_from_sequence_parallel_region,
            )

            x = gather_from_sequence_parallel_region(x, tp_axis, False)
        x = fused_layer_norm_affine(
            x, shared["final_ln_scale"], shared["final_ln_bias"], (H,), config.layernorm_eps
        )
        from apex_tpu.transformer.tensor_parallel.mappings import (
            copy_to_tensor_model_parallel_region,
        )

        x = copy_to_tensor_model_parallel_region(x, tp_axis)
        t = mb["targets"].transpose(1, 0)
        return jnp.mean(lm_head_loss(x, shared["embed"], t, config, tp_axis))

    def run_schedule(params, tokens, targets, stage_fn_, post_fn_):
        shared = {k: v for k, v in params.items() if k != "layers"}
        stages = params["layers"]
        B = tokens.shape[0]
        mb = {
            "tokens": tokens.reshape(num_microbatches, B // num_microbatches, -1),
            "targets": targets.reshape(num_microbatches, B // num_microbatches, -1),
        }
        if vpp > 1:
            loss, (g_shared, g_stage) = forward_backward_pipelining_with_interleaving(
                pre_fn, stage_fn_, post_fn_, shared, stages, mb,
                virtual_pipeline_model_parallel_size=vpp, axis_name=pp_axis,
                stage_has_aux=config.moe,
            )
        else:
            loss, (g_shared, g_stage) = forward_backward_pipelining_without_interleaving(
                pre_fn, stage_fn_, post_fn_, shared, stages, mb, axis_name=pp_axis,
                stage_has_aux=config.moe,
            )
        return loss, {**g_shared, "layers": g_stage}

    def sync_loss_and_grads(loss, grads):
        if sp:
            grads = sp_grad_sync(grads, tp_axis)
        if cp_axis is not None:
            # each cp rank's loss/grads cover its local sequence chunk
            loss = jax.lax.pmean(loss, cp_axis)
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, cp_axis), grads)
        if dp_axis is not None:
            loss = jax.lax.pmean(loss, dp_axis)
            if not zero_opt:
                if config.moe:
                    # expert grads are dp-SHARDED (the all_to_all already
                    # delivered the dp-summed cotangents): divide, never
                    # pmean (which would mix different experts' grads)
                    from apex_tpu.transformer.expert_parallel import EXPERT_PARAM_KEYS

                    inv = 1.0 / jax.lax.axis_size(dp_axis)
                    moe_g = {
                        k: (v * inv if k in EXPERT_PARAM_KEYS
                            else jax.lax.pmean(v, dp_axis))
                        for k, v in grads["layers"]["moe"].items()
                    }
                    rest = {**grads, "layers": {k: v for k, v in grads["layers"].items() if k != "moe"}}
                    grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp_axis), rest)
                    grads["layers"]["moe"] = moe_g
                else:
                    grads = jax.tree.map(lambda g: jax.lax.pmean(g, dp_axis), grads)
        # ZeRO: grads stay LOCAL — the optimizer's per-bucket
        # psum_scatter over dp IS the gradient sync (one reduce-scatter
        # per dtype bucket in grad_sync_dtype, fused with the update)
        return loss, grads

    if chaos is not None and step_guard is None:
        raise ValueError("chaos NaN injection needs step_guard (the "
                         "injection step counter lives in GuardState)")

    def _scaled_fns(factor):
        """(stage_fn, post_fn) with every backward seed scaled by
        ``factor`` — the loss-scale multiply, the chaos fault, or both
        folded into one scalar (the schedule seeds backward from
        post_fn's output, so scaling HERE scales every cotangent in the
        pipeline; the MoE aux loss enters inside the schedule and must
        ride the same scaled backward)."""
        def post_scaled(shared, x, mb_):
            return post_fn(shared, x, mb_) * factor

        if config.moe:
            def stage_scaled(stage_params, x):
                out, aux = stage_fn(stage_params, x)
                return out, aux * factor
        else:
            stage_scaled = stage_fn
        return stage_scaled, post_scaled

    def local_step(params, opt_state, tokens, targets):
        loss, grads = run_schedule(params, tokens, targets, stage_fn, post_fn)
        loss, grads = sync_loss_and_grads(loss, grads)
        if clip_grad_norm is not None:
            new_params, new_state = optimizer.update(
                grads, opt_state, params, clip_norm=clip_grad_norm,
                sumsq_reduce=clip_reduce)
        else:
            new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, loss

    def guarded_local_step(params, opt_state, guard_state, tokens, targets):
        fault = chaos.grad_fault(guard_state.step) if chaos is not None else None
        if fault is not None:
            stage, post = _scaled_fns(fault)
        else:
            stage, post = stage_fn, post_fn
        loss, grads = run_schedule(params, tokens, targets, stage, post)
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_guard = _apply_guarded_update(
            grads, optimizer, opt_state, params, guard_sync_axes,
            step_guard, guard_state, clip_grad_norm=clip_grad_norm,
            clip_sumsq=clip_reduce,
        )
        return new_params, new_state, new_guard, loss

    def scaled_local_step(params, opt_state, scaler_state, tokens, targets):
        scale = scaler_state.loss_scale
        stage_scaled, post_scaled = _scaled_fns(scale)
        scaled_loss, grads = run_schedule(
            params, tokens, targets, stage_scaled, post_scaled
        )
        loss = scaled_loss / scale
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_scaler_state = _apply_scaled_update(
            loss_scaler, scaler_state, grads, optimizer, opt_state, params,
            guard_sync_axes, clip_grad_norm=clip_grad_norm,
            clip_sumsq=clip_reduce,
        )
        return new_params, new_state, new_scaler_state, loss

    def guarded_scaled_local_step(params, opt_state, scaler_state,
                                  guard_state, tokens, targets):
        scale = scaler_state.loss_scale
        fault = chaos.grad_fault(guard_state.step) if chaos is not None else None
        factor = scale * fault if fault is not None else scale
        stage_scaled, post_scaled = _scaled_fns(factor)
        scaled_loss, grads = run_schedule(
            params, tokens, targets, stage_scaled, post_scaled
        )
        loss = scaled_loss / scale
        loss, grads = sync_loss_and_grads(loss, grads)
        new_params, new_state, new_scaler_state, new_guard = \
            _apply_scaled_update(
                loss_scaler, scaler_state, grads, optimizer, opt_state,
                params, guard_sync_axes,
                step_guard=step_guard, guard_state=guard_state,
                clip_grad_norm=clip_grad_norm, clip_sumsq=clip_reduce,
            )
        return new_params, new_state, new_scaler_state, new_guard, loss

    from apex_tpu.optimizers.fused_adam import AdamState

    # A ZeRO optimizer (DistributedFusedAdam/LAMB) brings its own flat
    # state sharding; call its init with param_specs=specs and
    # axis_sizes={tp:..., pp:...} so the state is sized for the local
    # (pp, tp) param shard and sharded over (model axes, dp).
    zero_opt = hasattr(optimizer, "state_partition_spec")
    if zero_opt and config.moe:
        raise NotImplementedError(
            "ZeRO + MoE expert sharding both claim the dp axis; not wired"
        )
    _check_zero_axis(zero_opt, optimizer, dp_axis)
    # stage-sharded (pp) and tp-sharded grads can overflow on one rank
    # only — every such axis must agree on the skip decision; ZeRO
    # (local dp grads) and MoE (dp-sharded expert grads) add dp
    guard_sync_axes = [tp_axis, pp_axis]
    if (zero_opt or config.moe) and dp_axis is not None:
        guard_sync_axes.append(dp_axis)
    if opt_state_spec is not None:
        sspec = opt_state_spec
    elif zero_opt:
        sspec = optimizer.state_partition_spec()
    else:
        sspec = AdamState(step=P(), exp_avg=specs, exp_avg_sq=specs, master=None)
    data_spec = P(dp_axis, cp_axis) if dp_axis is not None else P(None, cp_axis)

    donate = (0, 1) if donate_state else ()
    fn, in_specs, out_specs, stats_argnum = _step_variant(
        loss_scaler, step_guard,
        {(True, True): guarded_scaled_local_step,
         (True, False): scaled_local_step,
         (False, True): guarded_local_step,
         (False, False): local_step},
        specs, sspec, data_spec, telemetry=telemetry)
    if stats_argnum is not None:
        donate = (*donate, stats_argnum)
    sharded = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=donate)


def gpt_loss(
    params, tokens, targets, config: GPTConfig, axis_name: Optional[str] = None,
    cp_axis: Optional[str] = None, ep_axis: Optional[str] = None,
):
    """Mean causal-LM cross entropy (+ MoE aux loss when enabled).
    Uses vocab-parallel CE on a mesh.  With ``cp_axis`` the mean is over
    the LOCAL sequence chunk — combine across chunks with a pmean (the
    data-axis gradient calculus)."""
    t = targets.transpose(1, 0)  # (S, B)
    out = gpt_forward(params, tokens, config, axis_name, cp_axis, ep_axis,
                      return_aux=config.moe, return_hidden=True)
    hidden, aux = out if config.moe else (out, None)
    loss = lm_head_loss(hidden, params["embed"], t, config, axis_name)
    loss = jnp.mean(loss)
    if aux is not None:
        loss = loss + config.moe_aux_coef * aux
    return loss
