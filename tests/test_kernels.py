"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.freelist import FreeListState, init_freelist
from repro.core.hmq import schedule
from repro.core.packets import (FREE_ALL, OP_FREE, OP_MALLOC, OP_NOP,
                                OP_REFILL, RequestQueue)
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.paged_attention.ops import paged_decode_attention_op
from repro.kernels.support_core.ops import support_core_burst
from repro.kernels.support_core.ref import support_core_burst_ref


@pytest.mark.parametrize("B,KV,G,hd,ps,P,dtype", [
    (3, 2, 4, 32, 8, 5, jnp.float32),
    (2, 1, 8, 64, 16, 4, jnp.float32),
    (2, 4, 1, 128, 8, 6, jnp.bfloat16),   # MHA-style G=1
    (1, 2, 2, 16, 4, 3, jnp.float32),
])
@pytest.mark.parametrize("window", [1 << 30, 19])
def test_paged_attention_kernel(rng, B, KV, G, hd, ps, P, dtype, window):
    H = KV * G
    npages = B * P + 2
    q = jnp.asarray(rng.randn(B, H, hd), dtype)
    kp = jnp.asarray(rng.randn(npages, ps, KV, hd), dtype)
    vp = jnp.asarray(rng.randn(npages, ps, KV, hd), dtype)
    tables = jnp.asarray(rng.permutation(npages)[:B * P].reshape(B, P), jnp.int32)
    seq = jnp.asarray(rng.randint(1, P * ps - 1, size=B), jnp.int32)
    out_k = paged_decode_attention_op(q, kp, vp, tables, seq, window=window,
                                      interpret=True)
    out_r = paged_decode_attention_op(q, kp, vp, tables, seq, window=window,
                                      impl="ref")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("Tq,Tk,H,KV,hd,bq,bk,causal,window,dtype", [
    (32, 32, 4, 2, 32, 16, 16, True, 1 << 30, jnp.float32),
    (64, 64, 4, 1, 64, 32, 16, True, 24, jnp.float32),
    (32, 32, 2, 2, 32, 8, 8, False, 1 << 30, jnp.float32),
    (64, 64, 8, 2, 128, 32, 32, True, 1 << 30, jnp.bfloat16),
])
def test_flash_attention_kernel(rng, Tq, Tk, H, KV, hd, bq, bk, causal,
                                window, dtype):
    B = 2
    q = jnp.asarray(rng.randn(B, Tq, H, hd), dtype)
    k = jnp.asarray(rng.randn(B, Tk, KV, hd), dtype)
    v = jnp.asarray(rng.randn(B, Tk, KV, hd), dtype)
    a = flash_attention_op(q, k, v, causal=causal, window=window,
                           block_q=bq, block_k=bk, interpret=True)
    b = flash_attention_op(q, k, v, causal=causal, window=window, impl="ref")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("Q,C,N,R,scarce", [
    (16, 2, 32, 4, False), (64, 4, 128, 8, False), (32, 3, 16, 4, True),
])
def test_fused_support_core_kernel(rng, Q, C, N, R, scarce):
    """The fused burst kernel (interpret) vs its jnp scheduled-step oracle:
    bit-identical metadata, grants, and grant flags on a mixed queue
    (mallocs, refills, single frees, FREE_ALL, nops) against a warmed-up
    pool.  The full multi-step differential suite lives in
    tests/test_support_core_kernel.py; this is the kernels-layer parity
    smoke alongside the other Pallas kernels."""
    caps = [int(c) for c in (rng.randint(2, max(3, N // 4), C) if scarce
                             else rng.randint(N // 2, N + 1, C))]
    state = init_freelist(caps)
    # Warm the pool up through the oracle so frees hit owned blocks.
    warm = RequestQueue(
        op=jnp.full((Q,), OP_MALLOC, jnp.int32),
        lane=jnp.asarray(rng.randint(0, 8, Q), jnp.int32),
        size_class=jnp.asarray(rng.randint(0, C, Q), jnp.int32),
        arg=jnp.asarray(rng.randint(1, R + 1, Q), jnp.int32))
    warm, _ = schedule(warm)
    state, _, _ = support_core_burst_ref(state, warm, max_blocks_per_req=R)

    ops = rng.choice([OP_MALLOC, OP_REFILL, OP_FREE, OP_FREE, OP_NOP], Q)
    args = np.where(ops == OP_FREE,
                    np.where(rng.rand(Q) < 0.5, FREE_ALL, rng.randint(0, N, Q)),
                    rng.randint(1, R + 2, Q))           # incl. overwide
    queue = RequestQueue(op=jnp.asarray(ops, jnp.int32),
                         lane=jnp.asarray(rng.randint(0, 8, Q), jnp.int32),
                         size_class=jnp.asarray(rng.randint(0, C, Q), jnp.int32),
                         arg=jnp.asarray(args, jnp.int32))
    sched, _ = schedule(queue)
    st_k, blk_k, ok_k = support_core_burst(state, sched, max_blocks_per_req=R,
                                           interpret=True)
    st_r, blk_r, ok_r = support_core_burst_ref(state, sched,
                                               max_blocks_per_req=R)
    for field in FreeListState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st_k, field)),
                                      np.asarray(getattr(st_r, field)),
                                      err_msg=field)
    np.testing.assert_array_equal(np.asarray(blk_k), np.asarray(blk_r))
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
