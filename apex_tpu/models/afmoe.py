"""A window-and-full-attention, sparse-expert decoder (the ``afmoe``
block) for the TRAINING path.

The third model family: what :mod:`apex_tpu.models.gpt` trains is one
dense block, what :mod:`apex_tpu.models.mla_moe` serves has no loss.
This one is named for the published ``model_type`` whose block it is:

- **sandwich norms** — four RMSNorms a layer; the output of the mixer
  and of the FFN is normed BEFORE it is added: ``x += RMS(Attn(RMS(x)))``,
  ``x += RMS(FFN(RMS(x)))``;
- **gated grouped-query attention** — per-head RMSNorm on queries and
  keys (one gain vector of ``head_dim`` each), a sigmoid gate of the
  layer's input on the heads' outputs, ``num_key_value_heads`` shared
  key/value heads;
- **window and full layers mixed** (``layer_types``) — a
  ``sliding_attention`` layer rotates queries and keys (rotary, the
  whole head, ``rotate_half`` pairing) and sees the last
  ``sliding_window`` keys, its own among them; a ``full_attention``
  layer rotates nothing and sees every earlier key.  Both are
  :func:`apex_tpu.ops.attention.flash_attention`, the first with its
  static ``window``;
- **held sparse experts** — after ``num_dense_layers`` leading layers
  with a dense gated-SiLU FFN, a layer's FFN is a shared expert plus
  the routed experts this process HOLDS of a router over all
  ``num_experts`` (sigmoid scores, a choice-only bias, weights
  renormalised over the chosen and scaled):
  :func:`~apex_tpu.transformer.expert_parallel.held_experts_ffn` in its
  trainable form (static chunks of ``expert_buffer_rows`` rows, no
  assignment dropped, a backward of its own).  The bias is STATE, not a
  parameter: the step moves it after the optimizer from the per-expert
  load (:func:`~apex_tpu.transformer.expert_parallel
  .balance_bias_update`), and no optimizer's tree holds it.

The embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``), the
head is untied, no projection has a bias.  Parameters are a list of
per-layer dicts (layers differ in kind, so the loop over them is
unrolled and each layer is one ``jax.checkpoint``), matrices stored
``(in, out)``, the head ``(vocab, hidden)`` as the fused cross-entropy
wants it.  The tree's ``"state"`` entry holds what no optimizer
touches: the routers' biases and the device-side counters
(:data:`COUNTER_NAMES`).

:meth:`AFMoEConfig.train_family` hands
:func:`apex_tpu.models.gpt.make_train_step` what it needs of a family:
the loss, the parameter specs, which leaves are state and which are
experts, and the state's update.  Tensor parallelism and serving do not
exist for this family (ROADMAP, Queue 2).
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.models._remat import remat_layer, validate_policy
from apex_tpu.models.mla_moe import _gated_ffn
from apex_tpu.transformer.expert_parallel import (
    balance_bias_update, expert_buffer_rows, held_experts_ffn,
)

__all__ = ["AFMoEConfig", "AFMoETrainFamily", "COUNTER_NAMES", "KINDS",
           "forward_hidden", "init_params", "loss_and_aux"]

#: a layer's mixer, by the published ``layer_types`` names
KINDS = ("sliding_attention", "full_attention")

#: the device-side counters a train step adds to, in the order of the
#: carried int32 vector ``params["state"]["counters"]``; every one is a
#: sum over the expert layers of a step, summed over steps:
#: assignments computed here; assignments of all tokens (``top_k`` a
#: token); held experts with at least one; the largest and the (floor
#: of the) mean load over ALL the router's experts; rows the static
#: buffers hold, every chunk walked; chunks walked beyond the first
COUNTER_NAMES = ("moe_assignments_held", "moe_assignments_all",
                 "moe_experts_hit", "moe_load_max", "moe_load_mean",
                 "moe_buffer_rows", "moe_spill_chunks", "steps")

_EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


@dataclasses.dataclass(frozen=True)
class AFMoEConfig:
    """Shapes and constants under the published config's names.
    ``num_experts`` is the ROUTER's width; ``held_start``/``held_count``
    say which of those experts this process holds (``held_count=None``:
    all of them)."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    #: the mixer of every layer; None: ``full_attention`` every
    #: ``global_attn_every_n_layers``-th layer, windows between
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    held_start: int = 0
    held_count: Optional[int] = None
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    #: False: a dense masked softmax (the kernels' plain twin)
    use_flash_attention: bool = False
    #: ``flash_attention``'s ``impl``, or "interpret" (the Pallas
    #: kernels through the interpreter, for the CPU tests)
    attn_impl: str = "auto"
    fused_ce: bool = False
    fused_ce_chunk: int = 128
    fused_ce_impl: Optional[str] = None
    #: every layer is one ``jax.checkpoint`` under this policy
    remat_policy: str = "full"
    #: the grouped matmuls' ``impl`` ("auto": megablox on a TPU)
    expert_impl: str = "auto"

    def __post_init__(self):
        validate_policy(self.remat_policy)
        kinds = self.kinds
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(KINDS):
            raise ValueError(f"layer_types must name one of {KINDS} for "
                             f"each of the {self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide into "
                             "num_key_value_heads groups")
        if self.held.stop > self.num_experts or len(self.held) < 1:
            raise ValueError(f"held {self.held} outside the router's "
                             f"{self.num_experts} experts")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers outside the layers")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "AFMoEConfig":
        """From a published ``config.json`` dict (``model_type:
        afmoe``).  All the experts the config counts are held; one
        chip's share of a wider router is the caller's to say, by
        overriding ``num_experts`` (the router's width) together with
        ``held_start``/``held_count``.  The router this family builds
        is the published one: sigmoid scores, normalised and scaled
        weights, one group.  Any field may be overridden."""
        for key, want in (("score_func", "sigmoid"), ("route_norm", True),
                          ("n_group", 1), ("topk_group", 1),
                          ("hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if conf.get(key, want) != want:
                raise NotImplementedError(
                    f"afmoe with {key}={conf[key]!r}: only {want!r} is "
                    f"built")
        kw = {f.name: conf[f.name] for f in dataclasses.fields(cls)
              if f.name in conf}
        if kw.get("layer_types") is not None:
            kw["layer_types"] = tuple(kw["layer_types"])
        kw["rope_theta"] = float(conf.get("rope_theta", 10000.0))
        kw.update(overrides)
        return cls(**kw)

    @property
    def kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        n = self.global_attn_every_n_layers
        return tuple(KINDS[1] if (i + 1) % n == 0 else KINDS[0]
                     for i in range(self.num_hidden_layers))

    @property
    def held(self) -> range:
        count = (self.num_experts - self.held_start
                 if self.held_count is None else self.held_count)
        return range(self.held_start, self.held_start + count)

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def buffer_rows(self, tokens: int) -> int:
        """Rows of the expert layer's static chunk at ``tokens`` a
        step."""
        return expert_buffer_rows(tokens, self.num_experts_per_tok,
                                  len(self.held), self.num_experts)

    def train_family(self) -> "AFMoETrainFamily":
        """What ``make_train_step`` asks of a model family."""
        return AFMoETrainFamily(self)


# ------------------------------------------------------------ parameters
def param_shapes(c: AFMoEConfig) -> Dict:
    """The parameter tree's shapes (leaf: a tuple)."""
    H, d = c.hidden_size, c.head_dim
    nq, nkv = c.num_attention_heads * d, c.num_key_value_heads * d
    Fs = c.moe_intermediate_size * c.num_shared_experts
    F, n_held = c.moe_intermediate_size, len(c.held)
    layers = []
    for i in range(c.num_hidden_layers):
        p = {"norm1": (H,), "norm2": (H,), "norm3": (H,), "norm4": (H,),
             "wq": (H, nq), "wk": (H, nkv), "wv": (H, nkv), "wg": (H, nq),
             "wo": (nq, H), "q_norm": (d,), "k_norm": (d,)}
        if i < c.num_dense_layers:
            p.update(w_gate=(H, c.intermediate_size),
                     w_up=(H, c.intermediate_size),
                     w_down=(c.intermediate_size, H))
        else:
            p.update(router=(H, c.num_experts), ws_gate=(H, Fs),
                     ws_up=(H, Fs), ws_down=(Fs, H),
                     we_gate=(n_held, H, F), we_up=(n_held, H, F),
                     we_down=(n_held, F, H))
        layers.append(p)
    return {"embed": (c.vocab_size, H), "head": (c.vocab_size, H),
            "final_norm": (H,), "layers": layers,
            "state": state_shapes(c)}


def state_shapes(c: AFMoEConfig) -> Dict:
    return {"router_bias": (c.num_moe_layers, c.num_experts),
            "last_load": (c.num_moe_layers, c.num_experts),
            "counters": (len(COUNTER_NAMES),)}


def init_state(c: AFMoEConfig) -> Dict:
    """The state no optimizer touches: the routers' biases (0), the
    load of the last step by layer and expert, the counters (0)."""
    shapes = state_shapes(c)
    return {"router_bias": jnp.zeros(shapes["router_bias"], jnp.float32),
            "last_load": jnp.zeros(shapes["last_load"], jnp.int32),
            "counters": jnp.zeros(shapes["counters"], jnp.int32)}


def init_params(config: AFMoEConfig, key, std: float = 0.02) -> Dict:
    """Seeded parameters: matrices N(0, std), gains 1, state zero."""
    shapes = param_shapes(config)
    shapes.pop("state")
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = [jnp.ones(s, config.param_dtype) if len(s) == 1 else
           (jax.random.normal(k, s, jnp.float32) * std
            ).astype(config.param_dtype) for k, s in zip(keys, leaves)]
    params = jax.tree.unflatten(treedef, out)
    params["state"] = init_state(config)
    return params


# ---------------------------------------------------------------- pieces
def _head_norm(x, gain, eps):
    """RMSNorm over the head dimension (queries and keys)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _rms_norm(x, gain, c: AFMoEConfig):
    """RMSNorm over the hidden width: the fused op (the Pallas
    LayerNorm kernels in their ``rms`` form on a TPU)."""
    from apex_tpu.normalization import fused_rms_norm_affine

    return fused_rms_norm_affine(x, gain, (c.hidden_size,), c.rms_norm_eps)


def _attention(h, p, c: AFMoEConfig, kind: str):
    """Gated grouped-query attention of one layer on normed ``h`` (B,
    S, H): what is added to the stream before its post-norm."""
    from apex_tpu.ops.rope import apply_rope

    B, S, _ = h.shape
    cd, d = c.compute_dtype, c.head_dim
    n, nkv = c.num_attention_heads, c.num_key_value_heads
    proj = lambda w: jnp.matmul(h, w.astype(cd))
    heads = lambda t, nh: t.reshape(B, S, nh, d).transpose(0, 2, 1, 3)
    q = _head_norm(heads(proj(p["wq"]), n), p["q_norm"], c.rms_norm_eps)
    k = _head_norm(heads(proj(p["wk"]), nkv), p["k_norm"], c.rms_norm_eps)
    v = heads(proj(p["wv"]), nkv)
    window = None
    if kind == "sliding_attention":
        positions = jnp.arange(S)
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
        # a window that holds the whole sequence is the causal triangle
        window = c.sliding_window if c.sliding_window < S else None
    if not c.use_flash_attention:
        # the kernels' plain twin: a dense band mask, float32 softmax
        from apex_tpu.ops.attention import mha_reference

        o = mha_reference(q, k, v, causal=True, window=window)
    elif c.attn_impl == "interpret":
        from apex_tpu.ops.flash_attention_pallas import flash_attention_pallas

        o = flash_attention_pallas(q, k, v, causal=True, window=window,
                                   interpret=True)
    else:
        from apex_tpu.ops.attention import flash_attention

        o = flash_attention(q, k, v, causal=True, window=window,
                            impl=c.attn_impl)
    o = o.astype(cd).transpose(0, 2, 1, 3).reshape(B, S, n * d)
    return jnp.matmul(o * jax.nn.sigmoid(proj(p["wg"])), p["wo"].astype(cd))


def _expert_ffn(h, p, bias, c: AFMoEConfig):
    """An expert layer's FFN on (T, H): the held experts' routed share
    plus the shared expert.  Returns ``(out, counts)``."""
    cd = c.compute_dtype
    routed, counts = held_experts_ffn(
        h, {"router": p["router"], "router_bias": bias,
            **{k: p[k].astype(cd) for k in _EXPERT_LEAVES}},
        c.held, top_k=c.num_experts_per_tok, n_group=1, topk_group=1,
        scale=c.route_scale, impl=c.expert_impl,
        buffer_rows=c.buffer_rows(h.shape[0]))
    return routed + _gated_ffn(h, p["ws_gate"], p["ws_up"],
                               p["ws_down"]), counts


def _layer(x, p, bias, c: AFMoEConfig, kind: str):
    """One layer on the stream (B, S, H), sandwich norms.  ``bias``: the
    layer's router bias, None in a dense layer.  Returns ``(x, counts
    or None)``."""
    B, S, H = x.shape
    a = _attention(_rms_norm(x, p["norm1"], c), p, c, kind)
    x = x + _rms_norm(a, p["norm2"], c)
    h = _rms_norm(x, p["norm3"], c).reshape(B * S, H)
    if bias is None:
        m, counts = _gated_ffn(h, p["w_gate"], p["w_up"], p["w_down"]), None
    else:
        m, counts = _expert_ffn(h, p, bias, c)
    return x + _rms_norm(m.reshape(B, S, H), p["norm4"], c), counts


def _count_vector(counts):
    """One expert layer's contribution to the counters, in
    :data:`COUNTER_NAMES` order (``steps`` apart)."""
    load = counts["load"]
    return jnp.stack([
        counts["assignments_held"], counts["assignments_all"],
        counts["experts_hit"], jnp.max(load),
        jnp.sum(load) // load.shape[0], counts["buffer_rows"],
        counts["spill_chunks"], jnp.int32(0)]).astype(jnp.int32)


def forward_hidden(params, tokens, config: AFMoEConfig):
    """``tokens`` (B, S) -> the final-normed stream (B, S, H) in the
    compute dtype and ``aux``: ``load`` (expert layers, num_experts)
    int32, the assignments every expert got in each layer, and
    ``counted`` (the counters' vector for this step, ``steps`` 0)."""
    c = config
    cd = c.compute_dtype
    x = jnp.take(params["embed"].astype(cd), tokens, axis=0)
    if c.mup_enabled:
        x = x * jnp.asarray(math.sqrt(c.hidden_size), cd)
    biases = jax.lax.stop_gradient(params["state"]["router_bias"])
    loads, counted = [], jnp.zeros((len(COUNTER_NAMES),), jnp.int32)
    for i, (p, kind) in enumerate(zip(params["layers"], c.kinds)):
        moe = i >= c.num_dense_layers
        layer = remat_layer(
            lambda x, p, b, kind=kind: _layer(x, p, b, c, kind),
            c.remat_policy)
        x, counts = layer(x, p, biases[i - c.num_dense_layers] if moe
                          else None)
        if counts is not None:
            loads.append(counts["load"])
            counted = counted + _count_vector(counts)
    aux = {"load": (jnp.stack(loads) if loads else
                    jnp.zeros((0, c.num_experts), jnp.int32)),
           "counted": counted}
    return _rms_norm(x, params["final_norm"], c), aux


def loss_and_aux(params, tokens, targets, config: AFMoEConfig):
    """Mean next-token cross entropy over the held rows of the
    vocabulary (no auxiliary loss), and :func:`forward_hidden`'s
    ``aux``."""
    c = config
    x, aux = forward_hidden(params, tokens, c)
    x, t = x.transpose(1, 0, 2), targets.transpose(1, 0)   # (S, B, ...)
    if c.fused_ce and t.shape[0] % c.fused_ce_chunk == 0:
        from apex_tpu.ops.fused_ce import fused_lm_head_ce

        # the head in the compute dtype: the kernels' (rows, hidden)
        # tile of a float32 table of this width does not fit VMEM
        per_token = fused_lm_head_ce(
            x, params["head"].astype(c.compute_dtype), t, c.fused_ce_chunk,
            None, c.fused_ce_impl)
    else:
        logits = jnp.matmul(x.astype(jnp.float32),
                            params["head"].T.astype(jnp.float32))
        # out-of-range ids clamp, as the fused head's do
        t = jnp.clip(t, 0, c.vocab_size - 1)
        per_token = jax.scipy.special.logsumexp(logits, axis=-1) - \
            jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
    return jnp.mean(per_token), aux


# ------------------------------------------------------ the step's family
class AFMoETrainFamily:
    """What :func:`apex_tpu.models.gpt.make_train_step` asks of a model
    family that is not GPT (as ``served_model()`` is what the scheduler
    asks): the loss with its auxiliary outputs, the parameter specs,
    which leaves are STATE that no optimizer touches and how the step
    moves them, and which leaves are experts."""

    def __init__(self, config: AFMoEConfig):
        self.config = config

    def init_params(self, key):
        return init_params(self.config, key)

    def param_specs(self):
        """Every leaf whole on every device: the family has no tensor
        parallelism, and the held experts are this process's share of
        an expert group that no mesh axis spans."""
        from jax.sharding import PartitionSpec as P

        return jax.tree.map(lambda _: P(), param_shapes(self.config),
                            is_leaf=lambda x: isinstance(x, tuple))

    @staticmethod
    def split(params):
        """``(trainable, state)``: the optimizer's tree, and the rest."""
        return ({k: v for k, v in params.items() if k != "state"},
                params["state"])

    @staticmethod
    def merge(trainable, state):
        return {**trainable, "state": state}

    @staticmethod
    def is_expert_param(path) -> bool:
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        return any(n in _EXPERT_LEAVES for n in names)

    @staticmethod
    def weight_decay_group(path, leaf) -> str:
        """``FusedAdam(param_group_fn=...)``: gains take no decay."""
        return "gain" if leaf.ndim == 1 else "matrix"

    def loss(self, params, tokens, targets):
        return loss_and_aux(params, tokens, targets, self.config)

    def update_state(self, state, aux):
        """After the optimizer: every expert layer's bias moves by the
        balance rule from the step's load, the counters add the step's
        counts."""
        return {
            "router_bias": balance_bias_update(
                state["router_bias"], aux["load"],
                self.config.load_balance_coeff),
            "last_load": aux["load"],
            "counters": state["counters"] + aux["counted"].at[
                COUNTER_NAMES.index("steps")].set(1),
        }
