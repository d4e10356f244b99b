"""The dense llama-style decoder: everything the benchmark knows of its
shape.

* ``arch_config``: the program's ``ArchConfig`` for a configuration file;
* ``layout``: the weight leaves, under the names of the program's
  parameter tree (the harness checks the tree against the program's own
  ``init_params`` shapes);
* ``forward_gaps``: the plain float32 reference.  It follows the
  published block: RMSNorm (gain stored as ``gain - 1``), rotate-half
  RoPE, grouped-query causal softmax attention with scale
  ``1/sqrt(head_dim)``, SwiGLU MLP with ``w_in = [gate | up]``, final
  RMSNorm and an untied output head, every matrix product through
  ``bench.reference._mm``.  It runs layer by layer over blocks of
  sequences, so it fits beside nothing else on one chip;
* ``decode_flops``: model FLOPs of decode steps, the arithmetic of
  ``repro.launch.roofline.model_flops`` (2 N per token for the weights,
  plus 4 * kv_len * heads * head_dim per token per layer for QK^T and
  PV), copied here so the yardstick stays fixed.  N counts the weights
  that take part in a matrix product: every layer's projections and MLP,
  and the output head; the input embedding is a lookup and is not
  counted;
* ``SMOKE``: the overrides that cut a configuration to the size of the
  CPU tests.

Nothing here imports the program but ``arch_config``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _mm, _rms, _rope

#: HF config key -> repro ArchConfig field, for the keys a cell may set
HF_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}
#: what the program's dense decoder fixes and the file has to state
PROGRAM_FIXED = {"rms_norm_eps": 1e-6, "hidden_act": "silu",
                 "tie_word_embeddings": False}

SMOKE = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=256)


def arch_config(model: dict):
    """The program's ``ArchConfig`` for a configuration file, with every
    key the file states applied to the registered architecture."""
    from repro.configs.base import get_config
    for key, want in PROGRAM_FIXED.items():
        if model[key] != want:
            raise ValueError(f"{key}={model[key]!r}: the program runs "
                             f"{want!r} only")
    base = get_config(model["arch"])
    if base.family != "dense" or base.attn_pattern != "full":
        raise ValueError(f"{model['arch']}: the reference is a dense decoder "
                         f"with full attention")
    return dataclasses.replace(
        base, **{f: model[k] for k, f in HF_FIELDS.items() if k in model})


def layout(model: dict) -> list[tuple[str, tuple[int, ...]]]:
    d, ff, v = (model["hidden_size"], model["intermediate_size"],
                model["vocab_size"])
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    n = model["num_hidden_layers"]
    return [
        ("embed", (v, d)),
        ("final_norm", (d,)),
        ("unembed", (d, v)),
        ("layers/ln_attn", (n, d)),
        ("layers/attn/wq", (n, d, h * hd)),
        ("layers/attn/wk", (n, d, kv * hd)),
        ("layers/attn/wv", (n, d, kv * hd)),
        ("layers/attn/wo", (n, h * hd, d)),
        ("layers/ln_mlp", (n, d)),
        ("layers/mlp/w_in", (n, d, 2 * ff)),   # [gate | up]
        ("layers/mlp/w_out", (n, ff, d)),
    ]


# ---- the plain float32 reference ------------------------------------------

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


# ---- the yardstick --------------------------------------------------------

def matmul_params(model: dict) -> int:
    d, ff, v = (model["hidden_size"], model["intermediate_size"],
                model["vocab_size"])
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return model["num_hidden_layers"] * per_layer + d * v


def decode_flops(model: dict, steps) -> float:
    """FLOPs of the decode steps ``steps`` (``bench.driver.Step``): every
    layer attends all of each lane's positions, so their sum over lanes
    and steps, ``kv_tokens``, is enough."""
    tokens = sum(s.active for s in steps)
    kv_tokens = sum(s.kv_tokens for s in steps)
    h, hd = model["num_attention_heads"], model["head_dim"]
    attn = 4.0 * kv_tokens * h * hd * model["num_hidden_layers"]
    return 2.0 * matmul_params(model) * tokens + attn
