"""Plain float32 forward of a dense decoder, independent of the program.

It follows the published llama-style block: RMSNorm (gain stored as
``gain - 1``), rotate-half RoPE, grouped-query causal softmax attention
with scale ``1/sqrt(head_dim)``, SwiGLU MLP with ``w_in = [gate | up]``,
final RMSNorm and an untied output head.  Every matrix product runs at
``Precision.HIGHEST``, so on the TPU it is a true float32 product.  It
runs layer by layer over blocks of sequences, so it fits beside nothing
else on one chip.

``quant="fp8"`` is the control: the same forward with every matrix
product's operands rounded to float8 e4m3 with a scale per row of the
left operand and per column of the right one (the step below the bf16
that the configurations state).
"""
from __future__ import annotations

from functools import partial

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


@partial(jax.jit, static_argnames=("model_key", "quant"))
def _layer(x, layers, li, model_key, quant):
    h_, kv_, hd, ff, eps, theta = model_key
    lw = jax.tree_util.tree_map(lambda w: w[li], layers)
    b, t, _ = x.shape
    h = _rms(x, lw["ln_attn"], eps)
    q = _mm("btd,de->bte", h, lw["attn"]["wq"], quant).reshape(b, t, h_, hd)
    k = _mm("btd,de->bte", h, lw["attn"]["wk"], quant).reshape(b, t, kv_, hd)
    v = _mm("btd,de->bte", h, lw["attn"]["wv"], quant).reshape(b, t, kv_, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    g = h_ // kv_
    k = jnp.repeat(k, g, axis=2)                 # query head j reads j // g
    v = jnp.repeat(v, g, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, quant, -1) / np.float32(np.sqrt(hd))
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, quant, -3).reshape(b, t, h_ * hd)
    x = x + _mm("bte,ed->btd", o, lw["attn"]["wo"], quant)
    h = _rms(x, lw["ln_mlp"], eps)
    gu = _mm("btd,df->btf", h, lw["mlp"]["w_in"], quant)
    gate, up = gu[..., :ff], gu[..., ff:]
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up,
                   lw["mlp"]["w_out"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, unembed, tokens, eps, quant):
    logits = _mm("btd,dv->btv", _rms(x, final_norm, eps), unembed, quant)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return best - got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _model_key(model: dict):
    return (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["intermediate_size"],
            float(model["rms_norm_eps"]), float(model["rope_theta"]))


def forward_gaps(model: dict, weights: dict, seqs: list[np.ndarray],
                 targets: list[np.ndarray], pad_to: int, block: int = 4,
                 quant: str | None = None):
    """Teacher-forced forward over each ``seqs[i]`` (prompt plus every
    served token but the last).  ``targets[i]`` are the tokens to judge at
    the last ``len(targets[i])`` positions.

    Returns, per sequence, the gap by which each target's logit lies below
    the best logit at its position, and the token this forward puts first
    there.
    """
    mk = _model_key(model)
    eps = float(model["rms_norm_eps"])
    n_layers = model["num_hidden_layers"]
    gaps, firsts = [], []
    for s in range(0, len(seqs), block):
        chunk = list(range(s, min(s + block, len(seqs))))
        toks = np.zeros((block, pad_to), np.int32)
        tgt = np.zeros((block, pad_to), np.int32)
        for r, i in enumerate(chunk):
            n, m = len(seqs[i]), len(targets[i])
            toks[r, :n] = seqs[i]
            tgt[r, n - m: n] = targets[i]
        x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0
                     ).astype(jnp.float32)
        for li in range(n_layers):
            x = _layer(x, weights["layers"], li, mk, quant)
        gap, first = _head(x, weights["final_norm"], weights["unembed"],
                           jnp.asarray(tgt), eps, quant)
        gap, first = np.asarray(gap), np.asarray(first)
        for r, i in enumerate(chunk):
            n, m = len(seqs[i]), len(targets[i])
            gaps.append(gap[r, n - m: n])
            firsts.append(first[r, n - m: n])
    return gaps, firsts
