"""What decides ``correct``: every number compared beside its limit.

* ``unfinished``   requests due in the window that never completed or
                   served fewer tokens than asked (limit 0);
* ``invariants``   ``validate_paged_kv`` I1-I6 failures on the allocator
                   state once the requests drained (limit 0);
* ``leaked_pages`` KV pages still in use once the requests drained,
                   beyond those the prefix cache holds (limit 0);
* ``logit_gap``    over a sample of finished requests drawn from the seed
                   (the one with the most output tokens always in it,
                   and half cache hits where the cache is on): the widest
                   gap by which a served token's logit lies below the
                   best logit at its position, both read from the float32
                   reference run over the prompt and the served tokens.
                   The first served token checks the prefill's logits,
                   every later one the paged decode through the block
                   tables the allocator granted.  Its limit, and the
                   readings it was set from, are in
                   ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import numpy as np

from . import spec

SAMPLE = 12      # requests compared with the reference in each run


def allocator_checks(eng) -> dict:
    from repro.core.paged_kv import validate_paged_kv
    try:
        validate_paged_kv(eng.kvcfg, eng.state.paged, tenants=eng.tenants,
                          cache=eng.cache)
        bad = 0
    except Exception as e:                      # the check's own error type
        print(f"invariant failure: {e}", flush=True)
        bad = 1
    kv = eng.tenants.kv.size_class
    used = int(np.asarray(eng.state.paged.alloc.used)[kv])
    cached = eng.cache.pages if eng.cache is not None else 0
    active = int(np.asarray(eng.state.paged.active).sum())
    return {"invariants": bad, "leaked_pages": used - cached + active}


def unfinished(records) -> int:
    return sum(not r.done or len(r.output) != r.max_new_tokens
               for r in records.values())


def sample(records, seed: int, k: int = SAMPLE) -> list:
    done = [r for r in records.values() if r.done]
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    longest = max(done, key=lambda r: (r.max_new_tokens, r.prompt_len))
    rest = [r for r in done if r is not longest]
    hits = [r for r in rest if r.cached_len]
    misses = [r for r in rest if not r.cached_len]
    n_miss = min(len(misses), k - 1 - min(len(hits), (k - 1) // 2))
    n_hit = min(len(hits), k - 1 - n_miss)
    draw = lambda pool, n: [pool[i] for i in
                            rng.choice(len(pool), n, replace=False)]
    return [longest] + draw(hits, n_hit) + draw(misses, n_miss)


def logit_gaps(model: dict, seed: int, recs: list, pad_to: int,
               quant: str | None = None):
    """Per request: the reference's gap at each served token, and (with
    ``quant``) the gap at the token the lower precision puts first."""
    from .weights import make_weights
    forward_gaps = spec.family(model).forward_gaps
    weights = make_weights(model, seed)
    seqs = [np.concatenate([r.tokens, np.asarray(r.output[:-1], np.int32)])
            for r in recs]
    served = [np.asarray(r.output, np.int32) for r in recs]
    gaps, _ = forward_gaps(model, weights, seqs, served, pad_to)
    if quant is None:
        return gaps, None
    _, firsts = forward_gaps(model, weights, seqs, served, pad_to,
                             quant=quant)
    ctrl, _ = forward_gaps(model, weights, seqs, firsts, pad_to)
    return gaps, ctrl
