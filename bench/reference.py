"""Float32 arithmetic shared by the plain references under
``bench/families/``, independent of the program.

Every matrix product (``_mm``) runs at ``Precision.HIGHEST``, so on the
TPU it is a true float32 product.  ``_rms`` is RMSNorm with the gain
stored as ``gain - 1``; ``_rope`` is rotate-half RoPE over axis -3.

``quant="fp8"`` is the control: every matrix product's operands rounded
to float8 e4m3 with a scale per row of the left operand and per column of
the right one (the step below the bf16 that the configurations state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a, b, quant, b_axis: int = -2):
    """``einsum`` in float32; ``b_axis`` is b's contracted axis (a's is
    its last)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    t, hd = x.shape[-3], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv           # [T, hd/2]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
