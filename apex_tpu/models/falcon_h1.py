"""Falcon-H1: a decoder whose every layer mixes by attention AND by a
Mamba-2 state-space recurrence, in parallel, served.

The fifth served family (docs/inference.md), and a file of its own: it
shares :mod:`apex_tpu.ops` and :mod:`apex_tpu.inference` with the other
families and no block code, so nothing on the latent family's path
(``mla_moe._block``, its scan over segments) gains a branch for it
(ROADMAP D6).  What is different from all of them:

- **a layer's mixer is the SUM of two branches over one normed input**:
  grouped-query attention (rotary over the whole head, no bias) and a
  Mamba-2 mixer (:mod:`apex_tpu.ops.ssd`), each with its own output
  matrix, both added to the stream before the gated MLP;
- **a layer therefore holds BOTH kinds of cache entry**: its keys and
  values as columns of the paged pools ``k`` and ``v``, and a recurrent
  state ``ssm_state`` (heads x head size x state size, float32) with
  the short convolution's tail ``ssm_conv`` as rows of a decode slot
  (:class:`apex_tpu.inference.kv_cache.PerSlot`); a prefill and a decode
  step write all four;
- **muP multipliers**: constants of the published config on the
  embedding, the keys, both mixers' inputs and outputs, the five
  segments of the state-space input projection, the MLP's gate and
  output, and the logits.  They scale ACTIVATIONS here; the matrices
  stay as published.

The layer (``h`` the stream, every norm an RMSNorm with a gain)::

    x = norm(h; attn_norm)
    q, k, v = (x a_in) Wq, ((x a_in) Wk) key_mult, (x a_in) Wv
    A = (softmax(rope(q) rope(k)^T / sqrt(d), causal) v) Wo * a_out
    u = ((x s_in) W_in) * mup_vector     [z | x' | B | C | dt]
    xBC = silu(conv([x' | B | C]) + conv_b)     causal, depthwise
    dt = softplus(dt + dt_bias);  S = exp(-exp(a_log) dt) S + dt x' B^T
    y = S C + D x';  y = groupnorm(y silu(z); mamba_norm)
    M = (y W_out) * s_out
    h = h + A + M
    x2 = norm(h; ffn_norm)
    h = h + ((silu((x2 Wg) m_gate) * (x2 Wu)) Wd) * m_down

and ``logits = (norm(h; final_norm) W_head^T) * lm_head_multiplier``.
``groupnorm`` is an RMSNorm over each of ``mamba_n_groups`` equal parts
of the ``mamba_d_ssm`` channels (``mamba_rms_norm``, the gate applied
before the norm).

Norm gains, ``a_log``, ``dt_bias``, ``d_skip``, the convolution's filter
and bias, every softplus, exp and norm and the whole recurrence are
float32; matrices, activations, cached keys and values and the
convolution's tail are the compute dtype.  The layers are ONE
``lax.scan`` (every layer is alike) whose carry holds the pools and the
per-slot entries: each is written in place through an aliased kernel
(``apex_kv_write``, ``apex_ssd_decode``, ``apex_kda_conv_step``), and no
XLA op of a step produces a value of their size.

The hidden state that :func:`forward` (``return_hidden``) and
:func:`forward_decode` hand to the sampling head is final-normed AND
scaled by ``lm_head_multiplier``: the head then multiplies by the plain
matrix, as for every family.  Tensor-parallel and training variants do
not exist; a multi-position update of the state (speculative verify,
chunked prefill) and state snapshots (prefix sharing) are ROADMAP,
Queue 2.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.rope import apply_rope, apply_rope_at

__all__ = ["COUNTER_NAMES", "FLOAT32_LEAVES", "FalconH1Config",
           "FalconH1Served", "forward", "forward_decode", "init_params",
           "param_shapes"]

#: the device-side counter of the decode step: state updates, active
#: slots x layers a step
COUNTER_NAMES = ("ssm_state_updates",)
#: leaves kept in float32 whatever ``param_dtype``
FLOAT32_LEAVES = ("attn_norm", "ffn_norm", "final_norm", "mamba_norm",
                  "a_log", "dt_bias", "d_skip", "conv_w", "conv_b")


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Shapes and constants under the published config's names."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: on the segments z, x, B, C, dt of the input projection
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    #: on the gate's pre-activation and on the MLP's output
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads x "
                f"mamba_d_head ({self.mamba_n_heads} x {self.mamba_d_head})")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt), mlp_multipliers 2 (gate, down)")

    @classmethod
    def from_published(cls, conf: Dict, **overrides) -> "FalconH1Config":
        """From a published ``config.json`` dict (``model_type:
        falcon_h1``).  The keys that pick the mechanism are held to what
        this file implements: every layer has attention, the state-space
        mixer and the MLP (``attn_layer_indices: null``,
        ``mamba_use_mlp``), the convolution has a bias and no projection
        has one, the gated norm is a grouped RMSNorm with the gate
        applied first, SiLU, an untied head, no rotary scaling.  Any
        field may be overridden."""
        want = {"model_type": "falcon_h1", "attn_layer_indices": None,
                "mamba_use_mlp": True, "mamba_conv_bias": True,
                "mamba_proj_bias": False, "mamba_rms_norm": True,
                "mamba_norm_before_gate": False, "attention_bias": False,
                "mlp_bias": False, "projectors_bias": False,
                "hidden_act": "silu", "tie_word_embeddings": False,
                "rope_scaling": None}
        for key, value in want.items():
            if conf.get(key, value) != value:
                raise ValueError(
                    f"config {key} = {conf[key]!r}: this file serves "
                    f"{key} = {value!r}")
        heads = conf["num_attention_heads"]
        kw = dict(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            intermediate_size=conf["intermediate_size"],
            num_hidden_layers=conf["num_hidden_layers"],
            num_attention_heads=heads,
            num_key_value_heads=conf.get("num_key_value_heads") or heads,
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            mamba_d_ssm=conf.get("mamba_d_ssm")
            or conf["mamba_expand"] * conf["hidden_size"],
            mamba_n_heads=conf["mamba_n_heads"],
            mamba_d_head=conf["mamba_d_head"],
            mamba_d_state=conf["mamba_d_state"],
            mamba_n_groups=conf["mamba_n_groups"],
            mamba_d_conv=conf["mamba_d_conv"],
            mamba_chunk_size=conf["mamba_chunk_size"],
            rms_norm_eps=conf["rms_norm_eps"],
            rope_theta=float(conf["rope_theta"]),
            max_position_embeddings=conf["max_position_embeddings"],
            ssm_multipliers=tuple(float(m) for m in conf["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in conf["mlp_multipliers"]),
            **{key: float(conf[key]) for key in (
                "embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier")})
        kw.update(overrides)
        return cls(**kw)

    @property
    def conv_channels(self) -> int:
        """Channels of the short convolution: x, B and C side by side."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_width(self) -> int:
        """Outputs of the input projection: z, x, B, C, dt."""
        return self.mamba_d_ssm + self.conv_channels + self.mamba_n_heads

    @property
    def conv_shape(self) -> Tuple[int]:
        """A slot's convolution tail of one layer: the ``conv - 1`` last
        inputs, oldest first, side by side as ONE row (what
        ``apex_kda_conv_step`` takes)."""
        return ((self.mamba_d_conv - 1) * self.conv_channels,)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        return (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state)

    @property
    def mup_vector(self):
        """The input projection's multipliers, one an output, float32."""
        gn = self.mamba_n_groups * self.mamba_d_state
        widths = (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                  self.mamba_n_heads)
        return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                                for w, m in zip(widths,
                                                self.ssm_multipliers)])

    def served_model(self) -> "FalconH1Served":
        return FalconH1Served(self)


# ------------------------------------------------------------- parameters
def param_shapes(c: FalconH1Config) -> Dict:
    """The parameter tree's shapes: the layers stacked on a leading
    axis, matrices input-major, ``wqkv`` the three attention projections
    side by side (queries, keys, values), ``conv_w`` ``(taps,
    channels)``, ``conv_w[taps - 1]`` the current input's."""
    L, H, F = c.num_hidden_layers, c.hidden_size, c.intermediate_size
    qkv = (c.num_attention_heads + 2 * c.num_key_value_heads) * c.head_dim
    return {
        "embed": (c.vocab_size, H), "head": (c.vocab_size, H),
        "final_norm": (H,),
        "layers": {
            "attn_norm": (L, H), "ffn_norm": (L, H),
            "wqkv": (L, H, qkv),
            "wo": (L, c.num_attention_heads * c.head_dim, H),
            "w_in": (L, H, c.in_width),
            "conv_w": (L, c.mamba_d_conv, c.conv_channels),
            "conv_b": (L, c.conv_channels),
            "a_log": (L, c.mamba_n_heads), "dt_bias": (L, c.mamba_n_heads),
            "d_skip": (L, c.mamba_n_heads), "mamba_norm": (L, c.mamba_d_ssm),
            "w_out": (L, c.mamba_d_ssm, H),
            "w_gate": (L, H, F), "w_up": (L, H, F), "w_down": (L, F, H)},
    }


def init_params(config: FalconH1Config, key) -> Dict:
    """Seeded parameters.  A matrix maps unit variance to unit variance
    AFTER the multipliers on its path (``N(0, 1 / fan_in)`` over the
    multiplier: the published multipliers shrink both mixers' outputs
    and the logits, and trained weights are as much larger), so that
    every branch moves the stream; gains ``1 + N(0, 0.02)``; the
    filter ``N(0, 1 / taps)``, its bias ``N(0, 0.1)``; ``exp(a_log)``
    uniform in [1, 16], ``softplus(dt_bias)`` log-uniform in [0.001,
    0.1], ``d_skip`` 1 (Mamba-2's reference initialisation).
    :data:`FLOAT32_LEAVES` float32, all else ``param_dtype``."""
    c = config
    m_gate, m_down = c.mlp_multipliers
    width = lambda *parts: jnp.concatenate(
        [jnp.full((w,), m, jnp.float32) for w, m in parts])
    d = c.head_dim
    # the multiplier on each OUTPUT of a matrix (inputs' folded in)
    on = {
        "embed": c.embedding_multiplier, "head": c.lm_head_multiplier,
        "wqkv": c.attention_in_multiplier * width(
            (c.num_attention_heads * d, 1.0),
            (c.num_key_value_heads * d, c.key_multiplier),
            (c.num_key_value_heads * d, 1.0)),
        "wo": c.attention_out_multiplier,
        "w_in": c.ssm_in_multiplier * c.mup_vector,
        "w_out": c.ssm_out_multiplier,
        "w_gate": m_gate, "w_up": 1.0, "w_down": m_down}
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name, k = path[-1].key, jax.random.fold_in(key, i)
        if name == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
            x = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
        elif name == "d_skip":
            x = jnp.ones(shape, jnp.float32)
        elif name.endswith("norm"):
            x = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif name == "conv_w":
            x = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        elif name == "conv_b":
            x = 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            fan_in = shape[-1] if name in ("embed", "head") else shape[-2]
            scale = (1.0 if name == "embed" else fan_in ** -0.5) / on[name]
            x = jax.random.normal(k, shape, jnp.float32) * scale
        out.append(x if name in FLOAT32_LEAVES else x.astype(c.param_dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ pieces
def _rms_norm(x, gain, eps, groups=1):
    """RMSNorm in float32 over each of ``groups`` equal parts of the
    last axis; the result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    g = xf.reshape(xf.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(xf.shape) * gain.astype(jnp.float32)).astype(x.dtype)


def _times(x, m):
    """``x * m`` for a static multiplier (1: ``x`` itself), rounded once."""
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _embed(params, tokens, c: FalconH1Config):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    return (x * c.embedding_multiplier).astype(c.compute_dtype)


def _qkv(x, p, c: FalconH1Config):
    """(T, H) normed rows -> q (T, heads, d), k, v (T, kv heads, d),
    unrotated; the key's multiplier applied."""
    cd, d = c.compute_dtype, c.head_dim
    y = jnp.matmul(_times(x, c.attention_in_multiplier),
                   p["wqkv"].astype(cd))
    nq, nk = c.num_attention_heads * d, c.num_key_value_heads * d
    q = y[:, :nq].reshape(-1, c.num_attention_heads, d)
    k = _times(y[:, nq:nq + nk], c.key_multiplier) \
        .reshape(-1, c.num_key_value_heads, d)
    return q, k, y[:, nq + nk:].reshape(-1, c.num_key_value_heads, d)


def _ssm_project(x, p, c: FalconH1Config):
    """(T, H) normed rows -> the gate ``z`` (T, d_ssm) float32, the
    convolution's inputs (T, conv_channels) in the compute dtype (what
    the tail caches) and the raw steps (T, heads) float32."""
    cd = c.compute_dtype
    u = jnp.matmul(_times(x, c.ssm_in_multiplier), p["w_in"].astype(cd)) \
        .astype(jnp.float32) * c.mup_vector
    d = c.mamba_d_ssm
    return u[:, :d], u[:, d:d + c.conv_channels].astype(cd), \
        u[:, d + c.conv_channels:]


def _ssm_inputs(conv, dt_raw, p, c: FalconH1Config):
    """The recurrence's inputs, float32: ``conv`` (T, conv_channels) the
    convolution's sum (before its bias and SiLU), ``dt_raw`` (T, heads).
    Returns ``x (T, heads, P), dt (T, heads), A (heads,), B, C (T, G,
    N)``."""
    T = conv.shape[0]
    G, N, d = c.mamba_n_groups, c.mamba_d_state, c.mamba_d_ssm
    y = jax.nn.silu(conv.astype(jnp.float32) + p["conv_b"])
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])
    return (y[:, :d].reshape(T, c.mamba_n_heads, c.mamba_d_head), dt,
            -jnp.exp(p["a_log"].astype(jnp.float32)),
            y[:, d:d + G * N].reshape(T, G, N),
            y[:, d + G * N:].reshape(T, G, N))


def _ssm_output(y, z, p, c: FalconH1Config):
    """The recurrence's outputs (T, heads, P) float32, gated by
    ``silu(z)``, normed a group, through ``w_out`` and its multiplier."""
    cd = c.compute_dtype
    y = y.reshape(y.shape[0], -1) * jax.nn.silu(z)
    y = _rms_norm(y, p["mamba_norm"], c.rms_norm_eps, c.mamba_n_groups)
    return _times(jnp.matmul(y.astype(cd), p["w_out"].astype(cd)),
                  c.ssm_out_multiplier)


def _rest(h, attn, ssm, p, c: FalconH1Config):
    """The block after its mixers: ``attn`` (T, heads, d) through ``wo``
    and its multiplier, both branches into the stream, the gated MLP."""
    cd = c.compute_dtype
    m_gate, m_down = c.mlp_multipliers
    a = jnp.matmul(attn.reshape(attn.shape[0], -1).astype(cd),
                   p["wo"].astype(cd))
    h = h + _times(a, c.attention_out_multiplier) + ssm
    x = _rms_norm(h, p["ffn_norm"], c.rms_norm_eps)
    gate = jax.nn.silu(_times(jnp.matmul(x, p["w_gate"].astype(cd)), m_gate))
    y = jnp.matmul(gate * jnp.matmul(x, p["w_up"].astype(cd)),
                   p["w_down"].astype(cd))
    return h + _times(y, m_down)


def _final(h, params, c: FalconH1Config):
    """Final-normed and scaled by the logits' multiplier (module doc)."""
    return _times(_rms_norm(h, params["final_norm"], c.rms_norm_eps),
                  c.lm_head_multiplier)


def forward(params, tokens, config: FalconH1Config, attn_impl: str = "auto",
            return_hidden: bool = False, return_cache: bool = False,
            token_mask=None):
    """Full forward of (B, S) ``tokens``.  Returns float32 logits (B, S,
    V), or with ``return_hidden`` the activations the head multiplies
    (B, S, H); with ``return_cache`` also what the layers cache, by
    cache name: ``k`` and ``v`` (L, B, S, kv heads, d), the rotated keys
    and the values, and ``ssm_state`` (L, B, heads, P, N) and
    ``ssm_conv`` ((L, B) + conv_shape) at each sequence's end.
    ``token_mask`` (B, S), a PREFIX of each row: positions past it leave
    the state untouched (``dt = 0``) and the convolution's tail is taken
    at the mask's end."""
    from apex_tpu.ops.attention import flash_attention
    from apex_tpu.ops.ssd import ssd_chunked

    c = config
    B, S = tokens.shape
    K = c.mamba_d_conv
    flash = {"auto": "auto", "pallas": "pallas"}.get(attn_impl, "scan")
    positions = jnp.arange(S, dtype=jnp.int32)
    length = jnp.full((B,), S, jnp.int32) if token_mask is None \
        else jnp.sum(token_mask, axis=1).astype(jnp.int32)
    zero = jnp.zeros(c.state_shape, jnp.float32)

    def layer(h, p):
        x = _rms_norm(h, p["attn_norm"], c.rms_norm_eps)
        q, k, v = _qkv(x, p, c)
        seq = lambda t: t.reshape((B, S) + t.shape[1:])
        bhsd = lambda t: seq(t).transpose(0, 2, 1, 3)
        q, k = apply_rope(bhsd(q), positions, c.rope_theta), \
            apply_rope(bhsd(k), positions, c.rope_theta)
        attn = flash_attention(q, k, bhsd(v), causal=True, impl=flash)
        attn = attn.transpose(0, 2, 1, 3).reshape(B * S, -1, c.head_dim)

        z, xbc, dt_raw = _ssm_project(x, p, c)
        x3 = xbc.reshape(B, S, -1)
        xp = jnp.pad(x3.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(p["conv_w"][j] * xp[:, j:j + S] for j in range(K))
        xs, dt, A, Bm, Cm = _ssm_inputs(conv.reshape(B * S, -1), dt_raw, p, c)
        if token_mask is not None:
            dt = dt * token_mask.reshape(B * S, 1)
        y, state = jax.vmap(lambda *a: ssd_chunked(
            a[0], a[1], A, a[2], a[3], p["d_skip"], zero,
            chunk=c.mamba_chunk_size))(seq(xs), seq(dt), seq(Bm), seq(Cm))
        ssm = _ssm_output(y.reshape((B * S,) + y.shape[2:]), z, p, c)
        at = length[:, None] - (K - 1) + jnp.arange(K - 1)[None]  # (B,K-1)
        tail = jnp.take_along_axis(x3, jnp.clip(at, 0, S - 1)[:, :, None],
                                   axis=1)
        tail = jnp.where((at >= 0)[:, :, None], tail, 0)
        kept = (k.transpose(0, 2, 1, 3), seq(v), state,
                tail.reshape((B,) + c.conv_shape)) if return_cache else None
        return _rest(h, attn, ssm, p, c), kept

    h, kept = jax.lax.scan(layer, _embed(params, tokens.reshape(B * S), c),
                           params["layers"])
    out = _final(h, params, c).reshape(B, S, -1)
    if not return_hidden:
        out = jnp.matmul(out.astype(jnp.float32),
                         params["head"].T.astype(jnp.float32))
    if not return_cache:
        return out
    return out, dict(zip(("k", "v", "ssm_state", "ssm_conv"), kept))


def forward_decode(params, tokens, positions, active, pools, page_tables,
                   config: FalconH1Config, attn_impl: str = "auto",
                   verify_width: int = 1, write_mask=None):
    """One token a slot over both caches (the contract of
    :func:`apex_tpu.models.gpt.forward_decode`).

    ``pools``: ``"k"`` and ``"v"``, (layers, pages, kv heads, d,
    page_size); ``"ssm_state"`` (layers, slots + 1, heads, P, N)
    float32 and ``"ssm_conv"`` ((layers, slots + 1) + conv_shape); and
    optionally ``"counters"``.  A layer writes the token's key and value
    into its pages (``apex_kv_write``) and attends over them, every
    group of query heads against its one key/value head
    (``apex_decode_attention``); it shifts the slot's convolution tail
    (``apex_kda_conv_step``) and updates the slot's state
    (``apex_ssd_decode``), both in place; an inactive slot's are left as
    they were.  Returns ``(hidden (B, H), pools)``, hidden as the head
    takes it."""
    from apex_tpu.inference.kv_cache import COUNTERS, write_decode_pools
    from apex_tpu.ops.decode_attention_pallas import decode_attention
    from apex_tpu.ops.kda import conv_step
    from apex_tpu.ops.ssd import ssd_decode

    c = config
    if verify_width != 1 or write_mask is not None:
        raise NotImplementedError(
            "the state-space update takes one position a slot a step and "
            "cannot be rolled back: speculative verify and chunked "
            "prefill are not built for this family (ROADMAP, Queue 2)")
    positions = positions.astype(jnp.int32)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)

    def body(carry, inp):
        h, k_pool, v_pool, state, tails = carry
        p, index = inp
        x = _rms_norm(h, p["attn_norm"], c.rms_norm_eps)
        q, k, v = _qkv(x, p, c)
        q = apply_rope_at(q, positions, c.rope_theta)
        k = apply_rope_at(k, positions, c.rope_theta)
        k_pool, v_pool = write_decode_pools(
            (k_pool, v_pool), (k, v), page_tables, positions, active,
            layer=index, impl=attn_impl)
        attn = decode_attention(q, k_pool, v_pool, page_tables, lengths,
                                impl=attn_impl, layer=index)

        z, xbc, dt_raw = _ssm_project(x, p, c)
        conv, tails = conv_step(xbc, p["conv_w"], tails, active, index,
                                impl=attn_impl)
        xs, dt, A, Bm, Cm = _ssm_inputs(conv, dt_raw, p, c)
        y, state = ssd_decode(xs, dt, A, Bm, Cm, p["d_skip"], state, active,
                              index, impl=attn_impl)
        h = _rest(h, attn, _ssm_output(y, z, p, c), p, c)
        return (h, k_pool, v_pool, state, tails), None

    L = c.num_hidden_layers
    (h, k_pool, v_pool, state, tails), _ = jax.lax.scan(
        body, (_embed(params, tokens, c), pools["k"], pools["v"],
               pools["ssm_state"], pools["ssm_conv"]),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    out = dict(pools, k=k_pool, v=v_pool, ssm_state=state, ssm_conv=tails)
    if COUNTERS in pools:
        out[COUNTERS] = pools[COUNTERS] + (
            L * jnp.sum(active, dtype=jnp.int32))[None]
    return _final(h, params, c), out


# ----------------------------------------------------------- served model
class FalconH1Served:
    """What :mod:`apex_tpu.inference` needs of this family (the
    served-model interface, docs/inference.md)."""

    #: one position a slot a step: no speculative verify, no chunks
    multi_position = False
    #: rotary positions: no learned table bounds a request
    max_positions = None
    #: the leaves that every served program reads only as
    #: ``leaf.astype(compute_dtype)``: the matrices.  Not
    #: :data:`FLOAT32_LEAVES` (float32 arithmetic), not ``embed``
    #: (gathered, then widened) or ``head`` (the sampling head's own)
    cast_once_leaves = ("wqkv", "wo", "w_in", "w_out", "w_gate", "w_up",
                        "w_down")
    counter_names = COUNTER_NAMES

    def __init__(self, config: FalconH1Config):
        self.config = config

    def cache_spec(self) -> Dict[str, tuple]:
        """Every layer has all four: ``k`` and ``v`` paged, ``ssm_state``
        (float32) and ``ssm_conv`` (the compute dtype) per slot."""
        from apex_tpu.inference.kv_cache import PerSlot

        c = self.config
        kv = (c.num_hidden_layers, c.num_key_value_heads, c.head_dim)
        return {"k": kv, "v": kv,
                "ssm_state": PerSlot(c.num_hidden_layers, c.state_shape,
                                     jnp.float32),
                "ssm_conv": PerSlot(c.num_hidden_layers, c.conv_shape,
                                    c.compute_dtype)}

    def head(self, params):
        return params["head"]

    def serving_params(self, params):
        from apex_tpu.inference.decode import cast_once

        return cast_once(params, self.cast_once_leaves,
                         self.config.compute_dtype)

    def prefill(self, params, prompt, prompt_len, attn_impl):
        """(1, S) padded prompt -> the head's input (S, 1, H) and what
        to cache, by name: the paged pools' columns (L, S, kv heads, d),
        the per-slot entries' values AT ``prompt_len`` (L, ...)."""
        S = prompt.shape[1]
        hidden, cache = forward(
            params, prompt, self.config, attn_impl=attn_impl,
            return_hidden=True, return_cache=True,
            token_mask=jnp.arange(S, dtype=jnp.int32)[None] < prompt_len)
        return hidden.transpose(1, 0, 2), {n: x[:, 0]
                                           for n, x in cache.items()}

    def decode(self, params, tokens, positions, active, pools, page_tables,
               attn_impl, verify_width=1, write_mask=None):
        return forward_decode(
            params, tokens, positions, active, pools, page_tables,
            self.config, attn_impl=attn_impl, verify_width=verify_width,
            write_mask=write_mask)
