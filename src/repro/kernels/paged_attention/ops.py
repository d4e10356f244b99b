"""Jitted public wrapper for the paged decode-attention kernel.

Handles layout adaptation from the serving engine's conventions
([B, H, hd] queries, [num_pages, L, ps, KV, hd] pools, NO_BLOCK sentinels)
to the kernel's per-layer grouped layout.  ``interpret`` defaults to False
(the TPU target); CPU validation passes ``interpret=True`` explicitly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...core.packets import NO_BLOCK
from ...models.transformer import FULL_WINDOW
from .paged_attention import paged_attention_kernel
from .ref import paged_attention_ref


@partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_decode_attention_op(
    q: jnp.ndarray,             # [B, H, hd]
    k_pages: jnp.ndarray,       # [num_pages, ps, KV, hd] (one layer's pool)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, P] int32, NO_BLOCK for empty slots
    seq_lens: jnp.ndarray,      # [B] int32 — cache length incl. current token
    window: int = FULL_WINDOW,
    impl: str = "kernel",
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV = k_pages.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    tables = jnp.where(block_tables == NO_BLOCK, 0, block_tables)
    win = jnp.full((1,), window, jnp.int32)
    if impl == "ref":
        out = paged_attention_ref(qg, k_pages, v_pages, tables, seq_lens, win)
    else:
        out = paged_attention_kernel(qg, k_pages, v_pages, tables, seq_lens,
                                     win, interpret=interpret)
    return out.reshape(B, H, hd)
