"""Seeded weights, made on the device in one jitted call, in bf16.

``layout`` lists the leaves of a dense decoder from its configuration
file, under the names of the program's parameter tree (the harness checks
the tree against the program's own ``init_params`` shapes).  Leaf ``i``
is drawn from ``fold_in(key, i)``, so the same seed gives the same
weights to the program and, later in the run, to the reference.  This
module imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
GAIN_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one beyond 32 bits."""
    lo, hi = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo) & 0x7FFFFFFF),
                              int(hi) & 0x7FFFFFFF)


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


def _draw(name: str, key, shape, dtype):
    if len(shape) == 1 or "norm" in name or "/ln_" in name:
        # stored as gain - 1: gains of 1 +- 0.1, so a program that drops
        # or misapplies them departs from the reference
        return (jax.random.normal(key, shape, jnp.float32) * GAIN_STD
                ).astype(dtype)
    std = EMBED_STD if name == "embed" else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def nest(flat: dict) -> dict:
    out: dict = {}
    for name, x in flat.items():
        *parents, leaf = name.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def make_weights(model: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The nested weight tree for ``model``, drawn from ``seed``."""
    lay = layout(model)

    @jax.jit
    def draw(key):
        return {name: _draw(name, jax.random.fold_in(key, i), shape, dtype)
                for i, (name, shape) in enumerate(lay)}

    return nest(draw(seed_key(seed)))
