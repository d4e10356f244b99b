"""Jitted public wrapper for the flash-attention kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "impl", "interpret"))
def flash_attention_op(
    q: jnp.ndarray,    # [B, Tq, H, hd]
    k: jnp.ndarray,    # [B, Tk, KV, hd]
    v: jnp.ndarray,
    causal: bool = True,
    window: int = 1 << 30,
    block_q: int = 128,
    block_k: int = 128,
    impl: str = "kernel",
    interpret: bool = False,
) -> jnp.ndarray:
    if impl == "ref":
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
