"""The plain reference against arithmetic written out by hand, and the
pieces ``correct`` leans on: rows walked in blocks, AdamW, the lower
precisions of the control."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench_tiny import MODEL

from cellbench import weights
from cellbench.reference import gpt2

MODEL = dict(MODEL, n_layer=2, n_embd=32, n_head=4, vocab_size=96,
             n_positions=16)


@pytest.fixture(scope="module")
def params():
    return weights.gpt2_weights(MODEL, weights.seed_key(2 ** 31 + 9))


def _numpy_logits(p, tokens):
    """GPT-2 for one sequence in float64 with explicit loops over heads
    and positions: nothing shared with the reference but the paper."""
    f = lambda a: np.asarray(a, np.float64)
    H, nh = MODEL["n_embd"], MODEL["n_head"]
    hd = H // nh
    ln = lambda x, g, b: (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * g + b
    x = f(p["wte"])[tokens] + f(p["wpe"])[:len(tokens)]
    B = {k: f(v) for k, v in p["blocks"].items()}
    for l in range(MODEL["n_layer"]):
        h = ln(x, B["ln_1.g"][l], B["ln_1.b"][l])
        q = h @ B["attn.wq"][l] + B["attn.bq"][l]
        k = h @ B["attn.wk"][l] + B["attn.bk"][l]
        v = h @ B["attn.wv"][l] + B["attn.bv"][l]
        ctx = np.zeros_like(x)
        for n in range(nh):
            sl = slice(n * hd, (n + 1) * hd)
            for i in range(len(tokens)):
                sc = q[i, sl] @ k[:i + 1, sl].T / math.sqrt(hd)
                w = np.exp(sc - sc.max())
                ctx[i, sl] = (w / w.sum()) @ v[:i + 1, sl]
        x = x + ctx @ B["attn.wo"][l] + B["attn.bo"][l]
        h = ln(x, B["ln_2.g"][l], B["ln_2.b"][l])
        h = h @ B["mlp.w_fc"][l] + B["mlp.b_fc"][l]
        h = 0.5 * h * (1 + np.tanh(math.sqrt(2 / math.pi)
                                   * (h + 0.044715 * h ** 3)))
        x = x + h @ B["mlp.w_proj"][l] + B["mlp.b_proj"][l]
    return ln(x, f(p["ln_f.g"]), f(p["ln_f.b"])) @ f(p["wte"]).T


def test_forward_matches_the_written_out_model(params):
    tokens = np.random.RandomState(0).randint(0, 96, size=12)
    with jax.default_matmul_precision("highest"):
        got = gpt2.logits_at(params, jnp.asarray(tokens), jnp.arange(12),
                             MODEL["n_head"])
    np.testing.assert_allclose(np.asarray(got), _numpy_logits(params, tokens),
                               atol=2e-5, rtol=0)


def test_loss_is_the_mean_cross_entropy(params):
    rng = np.random.RandomState(1)
    tok, tgt = rng.randint(0, 96, (2, 10)), rng.randint(0, 96, (2, 10))
    want = []
    for row, t in zip(tok, tgt):
        lg = _numpy_logits(params, row)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        want.extend(lse - lg[np.arange(10), t])
    with jax.default_matmul_precision("highest"):
        got = gpt2.loss(params, jnp.asarray(tok), jnp.asarray(tgt),
                        MODEL["n_head"])
    assert float(got) == pytest.approx(np.mean(want), abs=1e-5)


def test_rows_in_blocks_equal_the_whole_batch(params):
    rng = np.random.RandomState(2)
    tok = jnp.asarray(rng.randint(0, 96, (4, 8)))
    tgt = jnp.asarray(rng.randint(0, 96, (4, 8)))
    with jax.default_matmul_precision("highest"):
        whole = jax.value_and_grad(
            lambda p: gpt2.loss(p, tok, tgt, MODEL["n_head"]))(params)
        blocks = gpt2.loss_and_grads(params, tok, tgt, MODEL["n_head"],
                                     rows_per_block=2)
    assert float(whole[0]) == pytest.approx(float(blocks[0]), abs=1e-6)
    for a, b in zip(jax.tree.leaves(whole[1]), jax.tree.leaves(blocks[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_adamw_first_step_is_a_signed_step_plus_decay():
    p = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    g = {"w": jnp.asarray([0.3, -0.1, 0.0])}
    z = {"w": jnp.zeros(3)}
    new, m, v = gpt2.adamw_step(p, g, z, z, 1.0, lr=0.1, beta1=0.9,
                                beta2=0.999, eps=1e-8, weight_decay=0.01)
    want = np.asarray(p["w"]) - 0.1 * (np.sign(g["w"])
                                       + 0.01 * np.asarray(p["w"]))
    np.testing.assert_allclose(np.asarray(new["w"]), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m["w"]), 0.1 * np.asarray(g["w"]),
                               atol=1e-7)


@pytest.mark.parametrize("quant,lo,hi", [("bfloat16", 1e-5, 2e-2),
                                         ("float8_e4m3fn", 1e-3, 0.5)])
def test_lower_precisions_move_the_logits_by_their_rounding(params, quant,
                                                            lo, hi):
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 96, size=12))
    with jax.default_matmul_precision("highest"):
        ref = gpt2.logits_at(params, tokens, jnp.arange(12), MODEL["n_head"])
        low = gpt2.logits_at(params, tokens, jnp.arange(12), MODEL["n_head"],
                             quant=quant)
    err = float(jnp.max(jnp.abs(ref - low)))
    assert lo < err < hi


def test_an_unknown_precision_is_refused(params):
    with pytest.raises(ValueError, match="precision"):
        gpt2.logits_at(params, jnp.zeros(4, jnp.int32), jnp.arange(4), 4,
                       quant="int3")


def test_weights_repeat_and_a_big_seed_is_fine():
    a = weights.gpt2_weights(MODEL, weights.seed_key(2 ** 31 + 9))
    b = weights.gpt2_weights(MODEL, weights.seed_key(2 ** 31 + 9))
    c = weights.gpt2_weights(MODEL, weights.seed_key(9))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["wte"]), np.asarray(c["wte"]))
    assert a["blocks"]["attn.wq"].shape == (2, 32, 32)
    assert a["blocks"]["mlp.w_fc"].shape == (2, 32, 128)
