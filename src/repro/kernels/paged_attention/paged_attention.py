"""Paged decode attention — Pallas TPU kernel (flash-decoding over pages).

The production read path of the SpeedMalloc paged KV cache: one new token per
lane attends over that lane's pages, located through the *segregated
metadata* (block table, passed as a scalar-prefetch operand so Mosaic can
compute the HBM->VMEM page DMAs from it — metadata never occupies VMEM tiles
on the data path, the TPU analogue of "metadata stays in the support-core's
L1").

Grid: (lanes, num_page_slots); the page-slot axis is innermost and
accumulates an online softmax in VMEM scratch (FlashAttention-style m/l/acc
carry).  Each grid step DMAs exactly one page's K tile and V tile for ALL
kv heads, selected by ``block_tables[lane, slot]`` via the BlockSpec
index_map — freed/invalid slots are clamped to page 0 and masked by
position validity.  The K/V block is ``(1, ps, KV, hd)``: its two minor
dims are the pool's own, which the TPU tiling rule requires (a
``(1, ps, 1, hd)`` block is refused for any KV > 1), and the kernel walks
the heads with a static loop.

Convention: the current token's K/V are already written to the cache (ops.py
does the paged write first), so valid positions are ``pos <= seq_len`` with
``seq_len`` the pre-append length.

VMEM budget per step: Q tile [KV, G, hd] + K/V tiles [ps, KV, hd] each +
scratch [KV, G, hd] + [KV, G, 1] x2 — e.g. KV=32, G=1, hd=128, ps=16 in
bf16: ~150 KB with double buffering, far under the scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch operands
    block_tables_ref,   # [B, P] int32 (clamped: invalid -> 0)
    seq_lens_ref,       # [B] int32 (pre-append length; self token included)
    windows_ref,        # [1] int32 (attention window; FULL = 1<<30)
    # array operands
    q_ref,              # [1, KV, G, hd]
    k_ref,              # [1, ps, KV, hd]  — page selected by index_map
    v_ref,              # [1, ps, KV, hd]
    # outputs
    o_ref,              # [1, KV, G, hd]
    # scratch
    m_ref,              # [KV, G, 1] f32
    l_ref,              # [KV, G, 1] f32
    acc_ref,            # [KV, G, hd] f32
    *,
    page_size: int,
    num_slots: int,
):
    b = pl.program_id(0)
    slot = pl.program_id(1)
    KV, G, hd = acc_ref.shape

    @pl.when(slot == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    pos = slot * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    seq = seq_lens_ref[b]
    win = windows_ref[0]
    valid = (pos <= seq) & (pos > seq - win)             # [1, ps]

    for h in range(KV):
        q = q_ref[0, h].astype(jnp.float32)               # [G, hd]
        k = k_ref[0, :, h, :].astype(jnp.float32)         # [ps, hd]
        v = v_ref[0, :, h, :].astype(jnp.float32)         # [ps, hd]
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, ps]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h]                                 # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(slot == num_slots - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_kernel(
    q: jnp.ndarray,             # [B, KV, G, hd]
    k_pages: jnp.ndarray,       # [num_pages, ps, KV, hd]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, P] int32 (invalid slots clamped to 0)
    seq_lens: jnp.ndarray,      # [B] int32
    window: jnp.ndarray,        # [1] int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, KV, G, hd]."""
    B, KV, G, hd = q.shape
    ps = k_pages.shape[1]
    P = block_tables.shape[1]

    def q_map(b, i, *_):
        return (b, 0, 0, 0)

    def kv_map(b, i, block_tables_ref, seq_lens_ref, windows_ref):
        return (block_tables_ref[b, i], 0, 0, 0)

    kernel = functools.partial(_kernel, page_size=ps, num_slots=P)
    # scalar prefetch: block tables + seq lens + window ride in SMEM and feed
    # the index_map (requires the TPU-specific PrefetchScalarGridSpec).
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, KV, G, hd), q_map),
                pl.BlockSpec((1, ps, KV, hd), kv_map),
                pl.BlockSpec((1, ps, KV, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, KV, G, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, window, q, k_pages, v_pages)
