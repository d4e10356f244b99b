"""Set-up passes that compile what a cell's traffic will run.

The engine compiles one prefill per (bucket, cached prefix) group and one
decode step; its admission and release paths run op by op, with shapes
that follow how many requests one pass admits and from which buckets.
Each pass here submits chosen prompts, admits them through
``run_admission``, decodes one step and releases them, so the window
meets these programs already compiled:

* every prefill bucket the mix reaches, with 1 to ``admit_width``
  requests in it;
* every pair of those buckets, one request each;
* passes of 1 to ``lanes`` requests spread over the buckets;
* with a shared prefix and the cache on: the system prompts demoted into
  the cache, then every suffix bucket as a cache hit, 1 to
  ``admit_width`` at a time.  The cache stays warm for the window.
"""
from __future__ import annotations

import itertools

import numpy as np


def _buckets(lo: int, hi: int, scfg) -> list[tuple[int, int]]:
    """(bucket, a prompt length that lands in it) for lengths lo..hi."""
    from repro.serve.scheduler import pick_bucket
    out: dict[int, int] = {}
    for n in range(lo, hi + 1):
        out.setdefault(pick_bucket(n, scfg), n)
    return sorted(out.items())


def warm_up(eng, sched, mix, vocab_size: int, seed: int = 0) -> int:
    """Run the set-up passes; returns how many.  Leaves every lane free."""
    from repro.serve.engine import run_admission
    from repro.serve.scheduler import Request
    rng = np.random.default_rng(seed)
    scfg = sched.scfg
    spec = mix.spec
    rid = itertools.count(-1, -1)
    n_pass = 0

    def run_pass(prompts: list[np.ndarray]) -> None:
        nonlocal n_pass
        for p in prompts:
            sched.submit(Request(rid=next(rid), tokens=p, max_new_tokens=4))
        while sched.waiting:
            if not run_admission(eng, sched):
                raise RuntimeError("warm-up admission made no progress")
            if sched.running:
                sched.note_decode_step(eng.step())
            lanes = sorted(sched.running)
            kv = {ln: sched.kv_token_prefix(ln) for ln in lanes} \
                if eng.cache is not None else None
            eng.release(lanes, kv_tokens=kv)
            sched.complete(lanes)
        n_pass += 1

    def rand(n: int) -> np.ndarray:
        return rng.integers(0, vocab_size, n, dtype=np.int32)

    pre = spec.get("prefix")
    p_lo, p_hi = spec["prompt"]["lo"], spec["prompt"].get("hi",
                                                          spec["prompt"]["lo"])
    sys_len = pre["tokens"] if pre else 0
    miss = _buckets(sys_len + p_lo, sys_len + p_hi, scfg)
    width = scfg.admit_width
    for _, n in miss:
        for k in range(1, width + 1):
            run_pass([rand(n) for _ in range(k)])
    for (_, a), (_, b) in itertools.combinations(miss, 2):
        run_pass([rand(a), rand(b)])
    lens = itertools.cycle([n for _, n in miss])
    for k in range(1, min(scfg.max_lanes, width * len(miss)) + 1):
        run_pass([rand(next(lens)) for _ in range(k)])
    if pre and mix.prefix_cache:
        systems = {it.prefix_id: it.tokens[:sys_len] for it in mix.items}
        run_pass([np.concatenate([s, rand(p_lo)])
                  for s in systems.values()])
        hits = _buckets(p_lo, p_hi, scfg)
        first = next(iter(systems.values()))
        for _, n in hits:
            for k in range(1, width + 1):
                run_pass([np.concatenate([first, rand(n)])
                          for _ in range(k)])
        for (_, a), (_, b) in itertools.combinations(hits, 2):
            run_pass([np.concatenate([first, rand(a)]),
                      np.concatenate([first, rand(b)])])
    return n_pass
