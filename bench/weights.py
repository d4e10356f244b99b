"""Seeded weights, made on the device in one jitted call, in bf16.

The leaves and their shapes are the family's ``layout``
(``bench/families/<family>.py``).  Leaf ``i`` is drawn from
``fold_in(key, i)``, so the same seed gives the same weights to the
program and, later in the run, to the reference.  This module imports
nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import spec

EMBED_STD = 0.02
GAIN_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one beyond 32 bits."""
    lo, hi = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo) & 0x7FFFFFFF),
                              int(hi) & 0x7FFFFFFF)


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
    lay = spec.family(model).layout(model)

    @jax.jit
    def draw(key):
        return {name: _draw(name, jax.random.fold_in(key, i), shape, dtype)
                for i, (name, shape) in enumerate(lay)}

    return nest(draw(seed_key(seed)))
