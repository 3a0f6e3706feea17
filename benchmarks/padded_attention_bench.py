"""Padded (key-masked) attention: Pallas flash kernel vs dense softmax.

The workload the fmha contrib exists for (BERT-shaped padded batches,
reference ``apex/contrib/fmha``): B=8, H=16, S=512, D=64, bf16, ~70%
tokens valid.  Measures fwd and fwd+bwd wall time on the real chip.

Run: python benchmarks/padded_attention_bench.py
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.attention import NEG_INF, flash_attention


def dense_masked_attention(q, k, v, kv_mask):
    """The pre-round-3 fallback: materialize the S×S score matrix."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


INNER = 10  # chained iterations inside one jit dispatch (amortizes
            # the per-dispatch host cost away)


def timeit(step, q, iters=5):
    """step: q -> q-like.  Chains INNER applications inside one jit."""
    chained = jax.jit(lambda q: jax.lax.fori_loop(0, INNER, lambda _, x: step(x), q))
    jax.block_until_ready(chained(q))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = chained(q)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / (iters * INNER) * 1e3


def main(S=512):
    B, H, D = 8, 16, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    lengths = rng.randint(S // 2, S + 1, size=B)
    mask = jnp.asarray(np.arange(S)[None, :] < lengths[:, None])
    mf = mask[:, None, :, None].astype(jnp.bfloat16)

    def k_loss(q):
        o = flash_attention(q, k, v, causal=False, kv_mask=mask)
        return jnp.sum((o * mf).astype(jnp.float32) ** 2)

    def d_loss(q):
        o = dense_masked_attention(q, k, v, mask)
        return jnp.sum((o * mf).astype(jnp.float32) ** 2)

    t_kf = timeit(lambda q: flash_attention(q, k, v, causal=False, kv_mask=mask), q)
    t_df = timeit(lambda q: dense_masked_attention(q, k, v, mask), q)
    t_kb = timeit(lambda q: jax.grad(k_loss)(q), q)
    t_db = timeit(lambda q: jax.grad(d_loss)(q), q)

    print(f"B={B} H={H} S={S} D={D} bf16, mean valid {float(mask.mean()):.2f}")
    print(f"fwd:      kernel {t_kf:7.3f} ms   dense {t_df:7.3f} ms   speedup {t_df / t_kf:4.2f}x")
    print(f"fwd+bwd:  kernel {t_kb:7.3f} ms   dense {t_db:7.3f} ms   speedup {t_db / t_kb:4.2f}x")


if __name__ == "__main__":
    for s in (512, 2048):
        main(S=s)
