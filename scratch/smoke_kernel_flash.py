import sys; sys.path.insert(0, "/root/repo/src")
import jax, jax.numpy as jnp
import numpy as np
from repro.kernels.flash_attention.ops import flash_attention_op

rng = np.random.RandomState(1)
for (B, Tq, Tk, H, KV, hd, bq, bk, causal, window, dtype) in [
    (2, 32, 32, 4, 2, 32, 16, 16, True, 1 << 30, jnp.float32),
    (1, 64, 64, 4, 1, 64, 32, 16, True, 24, jnp.float32),
    (2, 32, 32, 2, 2, 32, 8, 8, False, 1 << 30, jnp.float32),
    (1, 64, 64, 8, 2, 128, 32, 32, True, 1 << 30, jnp.bfloat16),
]:
    q = jnp.asarray(rng.randn(B, Tq, H, hd), dtype)
    k = jnp.asarray(rng.randn(B, Tk, KV, hd), dtype)
    v = jnp.asarray(rng.randn(B, Tk, KV, hd), dtype)
    a = flash_attention_op(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
                           interpret=True)
    b = flash_attention_op(q, k, v, causal=causal, window=window, impl="ref")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=tol, atol=tol)
    print(f"Tq={Tq} H={H} KV={KV} hd={hd} causal={causal} win={window if window<1<<29 else 'inf'} {dtype.__name__}: OK")
print("FLASH ATTENTION KERNEL OK")
