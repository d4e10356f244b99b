import sys; sys.path.insert(0, "/root/repo/src")
import jax, jax.numpy as jnp
import numpy as np
from repro.kernels.paged_attention.ops import paged_decode_attention_op

rng = np.random.RandomState(0)
for (B, KV, G, hd, ps, P, dtype) in [
    (3, 2, 4, 32, 8, 5, jnp.float32),
    (2, 1, 8, 64, 16, 4, jnp.float32),
    (2, 4, 1, 128, 8, 6, jnp.bfloat16),   # MHA-style G=1
]:
    H = KV * G
    npages = B * P + 2
    q = jnp.asarray(rng.randn(B, H, hd), dtype)
    kp = jnp.asarray(rng.randn(npages, ps, KV, hd), dtype)
    vp = jnp.asarray(rng.randn(npages, ps, KV, hd), dtype)
    tables = jnp.asarray(rng.permutation(npages)[:B * P].reshape(B, P), jnp.int32)
    seq = jnp.asarray(rng.randint(1, P * ps - 1, size=B), jnp.int32)
    for window in (1 << 30, ps * 2 + 3):
        out_k = paged_decode_attention_op(q, kp, vp, tables, seq, window=window, impl="kernel",
                                          interpret=True)
        out_r = paged_decode_attention_op(q, kp, vp, tables, seq, window=window, impl="ref")
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
                                   rtol=tol, atol=tol)
    print(f"B={B} KV={KV} G={G} hd={hd} ps={ps} P={P} {dtype.__name__}: kernel==ref OK")
print("PAGED ATTENTION KERNEL OK")
