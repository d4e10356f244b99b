"""Zero-copy prefix-cache hits: refcounted copy-on-write page aliasing
(DESIGN.md §12).

The acceptance proofs of the aliasing tentpole:

* alias admission SPLICES cache-owned page ids into the lane's block table
  with a refcount bump — no K/V bytes move — and the exact I6 identity
  (refcount == block-table in-degree + cache/stash references) holds after
  every lifecycle op;
* a shared page released by several lanes in ONE merged burst decrements
  once per reference and returns to the free stack exactly once, at
  refcount 0 — never double-pushed;
* the paged-attention kernel and its jnp reference read mixed
  private/shared block tables natively: a page id appearing in two lanes'
  rows produces bit-identical output to an equivalent private-copy layout
  (ownership never enters the read path);
* serving in alias mode is BIT-IDENTICAL to copy mode (and cache-off) on a
  shared-system-prompt mix with ``cache_hit_copy_bytes == 0``, at one and
  at two engine shards;
* pinned (aliased) cache entries survive eviction pressure, and the sim
  replay reproduces the pin/unpin stream exactly.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, needs_hypothesis, settings, st

import repro.core.paged_kv as pkv
from repro.configs import smoke_config
from repro.core.paged_kv import CACHE_OWNER, PagedKVConfig, PrefixCache
from repro.kernels.paged_attention.ops import paged_decode_attention_op
from repro.models import init_params, make_paged_config
from repro.serve.engine import ServingEngine
from repro.serve.multi_engine import MultiEngine
from repro.serve.scheduler import Request, Scheduler, make_scheduler_config
from repro.sim.policies import replay_prefix_trace

PS = 4


def _seq(rng, n):
    return rng.randint(0, 97, size=n).astype(np.int32)


def _cfg(num_pages=16, max_lanes=2, per_lane=4):
    return PagedKVConfig(num_kv_layers=1, kv_heads=1, head_dim=2, page_size=PS,
                         num_pages=num_pages, max_lanes=max_lanes,
                         max_pages_per_lane=per_lane, dtype=jnp.float32,
                         stash_size=0)


def _kv(rng, b, t):
    return jnp.asarray(rng.randn(b, 1, t, 1, 2).astype(np.float32))


def _release(cfg, state, tenants, lanes=(), extra=None):
    pkts = np.full((cfg.max_lanes,), -1, np.int32)
    for i, l in enumerate(sorted(lanes)):
        pkts[i] = l
    state, _ = pkv.release_packets(cfg, state, jnp.asarray(pkts),
                                   tenants=tenants, extra_free=extra)
    return state


def _stack_ids(state, c=0):
    top = int(np.asarray(state.alloc.free_top)[c])
    return np.asarray(state.alloc.free_stack)[c, :top]


def _seed_cache(cfg, tenants, rng, toks):
    """Admit lane 0 with ``toks`` (full pages), demote every page into a
    fresh cache, release the lane — the canonical hit setup."""
    state = pkv.init_paged_kv(cfg, tenants=tenants)
    n = len(toks) // PS
    state, stats = pkv.admit_prefill_many(
        cfg, state, jnp.asarray([0], jnp.int32), _kv(rng, 1, len(toks)),
        _kv(rng, 1, len(toks)), jnp.asarray([len(toks)], jnp.int32),
        tenants=tenants)
    assert int(stats.failed) == 0
    cache = PrefixCache(PS, budget_pages=8)
    kept, skipped, ev = cache.insert(
        toks, np.asarray(state.block_tables)[0, :n])
    assert skipped == [] and ev == []
    state = state._replace(alloc=tenants.service.retag_blocks(
        state.alloc, tenants.kv, np.asarray(kept, np.int32), CACHE_OWNER))
    state = _release(cfg, state, tenants, lanes=[0])
    pkv.validate_paged_kv(cfg, state, tenants=tenants, cache=cache)
    return state, cache


# ---------------------------------------------------------------------------
# refcount lifecycle at the paged-KV layer
# ---------------------------------------------------------------------------

def test_alias_admission_splices_bumps_and_releases_once():
    cfg = _cfg()
    t = pkv.paged_tenants(cfg)
    rng = np.random.RandomState(0)
    toks = _seq(rng, 8)                           # 2 cached pages
    state, cache = _seed_cache(cfg, t, rng, toks)
    cl, shared = cache.probe(np.concatenate([toks, _seq(rng, 4)]))
    assert cl == 8 and len(shared) == 2

    # BOTH lanes alias the same 2-page prefix in one burst; each installs a
    # 4-token private suffix
    suf = [np.concatenate([toks, _seq(rng, 4)]) for _ in range(2)]
    state, stats = pkv.admit_prefill_many(
        cfg, state, jnp.asarray([0, 1], jnp.int32), _kv(rng, 2, 4),
        _kv(rng, 2, 4), jnp.asarray([4, 4], jnp.int32), tenants=t,
        prefix_blocks=jnp.asarray([shared, shared], jnp.int32),
        prefix_lens=jnp.asarray([8, 8], jnp.int32))
    assert int(stats.failed) == 0
    for s, n in zip(suf, (2, 2)):
        cache.alias(s, n)

    tbl = np.asarray(state.block_tables)
    refc = np.asarray(state.alloc.refcount)[0]
    assert list(tbl[0, :2]) == shared and list(tbl[1, :2]) == shared
    assert tbl[0, 2] != tbl[1, 2]                 # private suffix pages
    assert all(refc[b] == 3 for b in shared)      # cache + 2 lanes
    assert (np.asarray(state.seq_lens)[:2] == 12).all()
    assert cache.pinned == 2
    pkv.validate_paged_kv(cfg, state, tenants=t, cache=cache)

    # pinned entries are not evictable, even under explicit pressure
    assert cache.evict_pages(4) == []

    # ONE merged burst carries both lanes' releases: the shared pages see
    # TWO single-free decrements each plus the FREE_ALLs (which skip them,
    # owner CACHE_OWNER) — refcount drops to 1, nothing double-pushes
    cache.unalias(suf[0], 2)
    cache.unalias(suf[1], 2)
    state = _release(cfg, state, t, lanes=[0, 1], extra=shared + shared)
    refc = np.asarray(state.alloc.refcount)[0]
    owner = np.asarray(state.alloc.owner)[0]
    stack = _stack_ids(state)
    assert all(refc[b] == 1 and owner[b] == CACHE_OWNER for b in shared)
    assert not any(b in stack for b in shared)    # still cache-resident
    assert len(np.unique(stack)) == len(stack)    # never double-pushed
    state = state._replace(block_tables=jnp.asarray(
        np.full_like(np.asarray(state.block_tables), -1)))
    pkv.validate_paged_kv(cfg, state, tenants=t, cache=cache)

    # eviction finally returns each page exactly once
    evicted = cache.evict_pages(cache.pages)
    assert sorted(evicted) == sorted(shared)
    state = _release(cfg, state, t, extra=evicted)
    refc = np.asarray(state.alloc.refcount)[0]
    stack = _stack_ids(state)
    assert all(refc[b] == 0 for b in shared)
    assert int(np.asarray(state.alloc.used)[0]) == 0
    assert len(np.unique(stack)) == len(stack) == cfg.num_pages
    pkv.validate_paged_kv(cfg, state, tenants=t, cache=cache)


def test_i6_catches_a_leaked_alias_bump():
    """A refcount bump with no matching block-table/cache reference is a
    leak the exact I6 identity must refuse."""
    cfg = _cfg()
    t = pkv.paged_tenants(cfg)
    rng = np.random.RandomState(1)
    state, cache = _seed_cache(cfg, t, rng, _seq(rng, 8))
    blk = int(cache.blocks()[0])
    state = state._replace(alloc=t.service.bump_refcounts(
        state.alloc, t.kv, np.asarray([blk], np.int32)))
    from repro.core.freelist import FreelistInvariantError
    with pytest.raises(FreelistInvariantError, match="I6"):
        pkv.validate_paged_kv(cfg, state, tenants=t, cache=cache)


@needs_hypothesis
@settings(max_examples=15, deadline=None)
@given(st.data())
def test_hypothesis_i6_alias_lifecycle_trace(data):
    """Random admit/alias/release/demote/evict interleavings: the exact I6
    refcount identity, the I5 partition, and free-stack uniqueness hold
    after EVERY op, with pins shielding shared pages from eviction."""
    cfg = _cfg(num_pages=64, max_lanes=4, per_lane=6)
    t = pkv.paged_tenants(cfg)
    state = pkv.init_paged_kv(cfg, tenants=t)
    cache = PrefixCache(PS, budget_pages=16)
    rng = np.random.RandomState(data.draw(st.integers(0, 999)))
    pool = [_seq(rng, 8) for _ in range(2)]       # shared prompt prefixes
    running: dict[int, tuple] = {}                # lane -> (toks, aliased)

    def check():
        pkv.validate_paged_kv(cfg, state, tenants=t, cache=cache)
        stack = _stack_ids(state)
        assert len(np.unique(stack)) == len(stack)

    for _ in range(data.draw(st.integers(min_value=6, max_value=18))):
        op = data.draw(st.sampled_from(["admit", "admit", "release", "evict"]))
        if op == "admit" and len(running) < cfg.max_lanes:
            lane = min(set(range(cfg.max_lanes)) - set(running))
            toks = np.concatenate([
                pool[data.draw(st.integers(0, 1))],
                _seq(rng, data.draw(st.sampled_from([4, 8])))])
            cl, shared = cache.probe(toks)
            if cl and data.draw(st.booleans()):   # zero-copy alias admission
                s = len(toks) - cl
                state, stats = pkv.admit_prefill_many(
                    cfg, state, jnp.asarray([lane], jnp.int32),
                    _kv(rng, 1, s), _kv(rng, 1, s),
                    jnp.asarray([s], jnp.int32), tenants=t,
                    prefix_blocks=jnp.asarray([shared], jnp.int32),
                    prefix_lens=jnp.asarray([cl], jnp.int32))
                assert int(stats.failed) == 0
                cache.alias(toks, len(shared))
                running[lane] = (toks, list(shared))
            else:                                 # plain full-length install
                state, stats = pkv.admit_prefill_many(
                    cfg, state, jnp.asarray([lane], jnp.int32),
                    _kv(rng, 1, len(toks)), _kv(rng, 1, len(toks)),
                    jnp.asarray([len(toks)], jnp.int32), tenants=t)
                assert int(stats.failed) == 0
                running[lane] = (toks, [])
        elif op == "release" and running:
            lane = data.draw(st.sampled_from(sorted(running)))
            toks, aliased = running.pop(lane)
            extra = list(aliased)
            if data.draw(st.booleans()):          # demote before release
                n = len(toks) // PS
                row = np.asarray(state.block_tables)[lane, :n]
                kept, _skipped, ev = cache.insert(toks[: n * PS], row)
                if kept:
                    state = state._replace(alloc=t.service.retag_blocks(
                        state.alloc, t.kv, np.asarray(kept, np.int32),
                        CACHE_OWNER))
                extra += ev
            if aliased:
                cache.unalias(toks, len(aliased))
            state = _release(cfg, state, t, lanes=[lane],
                             extra=extra or None)
        elif op == "evict":
            blocks = cache.evict_pages(data.draw(st.integers(1, 4)))
            if blocks:
                state = _release(cfg, state, t, extra=blocks)
        check()

    # drain: release every lane, then the whole cache — the pool must come
    # back whole with every refcount at zero
    for lane in sorted(running):
        toks, aliased = running.pop(lane)
        if aliased:
            cache.unalias(toks, len(aliased))
        state = _release(cfg, state, t, lanes=[lane], extra=aliased or None)
        check()
    blocks = cache.evict_pages(cache.pages)
    if blocks:
        state = _release(cfg, state, t, extra=blocks)
    check()
    assert cache.pinned == 0
    assert int(np.asarray(state.alloc.used)[0]) == 0
    assert (np.asarray(state.alloc.refcount)[0] == 0).all()


# ---------------------------------------------------------------------------
# paged attention reads shared tables natively (kernel + ref)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_paged_attention_reads_shared_block_tables(rng, impl):
    """A page id appearing in TWO lanes' block tables (the aliased prefix)
    reads bit-identically to an equivalent layout where each lane owns a
    private copy of the page — the read path is pure ``pages[table[b, i]]``
    gathering, ownership never enters it.  This is why the tentpole needs
    NO kernel change."""
    B, KV, G, hd, ps, P = 2, 2, 2, 32, 8, 4
    npages = 12
    q = jnp.asarray(rng.randn(B, KV * G, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(npages, ps, KV, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(npages, ps, KV, hd), jnp.float32)
    seq = jnp.asarray([3 * ps, 3 * ps - 2], jnp.int32)

    # shared layout: pages 0,1 are the aliased prefix of BOTH lanes
    shared = jnp.asarray([[0, 1, 2, -1], [0, 1, 3, -1]], jnp.int32)
    # private layout: lane 1 reads copies (10, 11) of pages (0, 1)
    kp2 = kp.at[10].set(kp[0]).at[11].set(kp[1])
    vp2 = vp.at[10].set(vp[0]).at[11].set(vp[1])
    private = jnp.asarray([[0, 1, 2, -1], [10, 11, 3, -1]], jnp.int32)

    out_shared = paged_decode_attention_op(q, kp, vp, shared, seq, impl=impl,
                                           interpret=True)
    out_private = paged_decode_attention_op(q, kp2, vp2, private, seq,
                                            impl=impl, interpret=True)
    assert np.array_equal(np.asarray(out_shared), np.asarray(out_private))
    # and kernel agrees with ref on the shared layout itself
    out_ref = paged_decode_attention_op(q, kp, vp, shared, seq, impl="ref")
    np.testing.assert_allclose(np.asarray(out_shared), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# serving: copy vs alias differential, one and two shards
# ---------------------------------------------------------------------------

ARCH = "deepseek-7b"


@pytest.fixture(scope="module")
def dense():
    cfg = smoke_config(ARCH)
    params = init_params(cfg, dtype=jnp.float32)
    return cfg, params


def _shared_prefix_requests(cfg, n=6, prefix_len=40, tail=6):
    rng = np.random.RandomState(0)
    shared = rng.randint(0, cfg.vocab_size, size=prefix_len).astype(np.int32)
    return [Request(rid=rid, tokens=np.concatenate(
                [shared, np.random.RandomState(100 + rid).randint(
                    0, cfg.vocab_size, size=tail).astype(np.int32)]))
            for rid in range(n)]


def _serve_mode(cfg, params, mode, n=6, max_new=6):
    from repro.launch.serve import serve_loop
    kvcfg = make_paged_config(cfg, seq_len=128, lanes=2, page_size=8,
                              dtype=jnp.float32)
    scfg = make_scheduler_config(cfg, kvcfg, max_prompt_len=64)
    eng = ServingEngine(cfg, kvcfg, params, dtype=jnp.float32, sched_cfg=scfg,
                        prefix_cache=True, eviction="lru", prefix_alias=mode)
    sched = Scheduler(scfg)
    serve_loop(eng, sched, _shared_prefix_requests(cfg, n=n), max_new,
               verbose=False)
    assert not sched.waiting and not sched.failed
    return eng, {r.rid: list(r.output) for r in sched.finished}


def test_alias_serving_bit_identical_and_zero_copy(dense):
    cfg, params = dense
    eng_c, outs_c = _serve_mode(cfg, params, "copy")
    eng_a, outs_a = _serve_mode(cfg, params, "alias")
    sc, sa = eng_c.stats, eng_a.stats

    # same tokens, same hits — different install mechanics only
    assert outs_a == outs_c
    assert sa.cache_hits == sc.cache_hits and sa.cache_hits > 0

    # the zero-copy claim, measured: alias moved NO prefix K/V bytes and
    # spliced one page reference per cached page; copy moved bytes and
    # spliced nothing
    assert sa.cache_hit_copy_bytes == 0 and sa.aliased_pages > 0
    assert sc.cache_hit_copy_bytes > 0 and sc.aliased_pages == 0
    assert eng_a.prefix_alias == "alias" and eng_c.prefix_alias == "copy"

    # every pin was balanced by a release, and the exact I6 identity holds
    assert eng_a.cache.pinned == 0
    pkv.validate_paged_kv(eng_a.kvcfg, eng_a.state.paged,
                          tenants=eng_a.tenants, cache=eng_a.cache)

    # the sim replay reproduces the alias/unalias stream exactly
    c = eng_a.cache
    rep = replay_prefix_trace(c.trace, "lru", c.budget,
                              eng_a.kvcfg.page_size)
    assert rep == {"hits": c.hits, "misses": c.misses, "inserts": c.inserts,
                   "evictions": c.evictions, "dup_skips": c.dup_skips,
                   "pages": c.pages, "aliases": c.aliases}
    assert rep["aliases"] == sa.aliased_pages > 0


def test_multi_engine_alias_bit_identical(dense):
    """Two shards on ONE shared freelist, per-window I1–I6 validation: the
    alias mode must not move a token relative to copy mode."""
    cfg, params = dense
    kvcfg = make_paged_config(cfg, seq_len=128, lanes=2, page_size=8,
                              dtype=jnp.float32)
    scfg = make_scheduler_config(cfg, kvcfg, max_prompt_len=64)
    outs, stats = {}, {}
    for mode in ("copy", "alias"):
        me = MultiEngine(cfg, kvcfg, params, n_engines=2, sched_cfg=scfg,
                         quantum=3, prefix_cache=True, eviction="lru",
                         prefix_alias=mode)
        me.serve(_shared_prefix_requests(cfg, n=10), max_new_tokens=6,
                 validate=True)
        assert not me.failed
        outs[mode] = {r.rid: list(r.output) for r in me.finished}
        stats[mode] = [e.stats for e in me.engines]
        assert all(e.cache.pinned == 0 for e in me.engines)
    assert outs["alias"] == outs["copy"]
    assert sum(s.aliased_pages for s in stats["alias"]) > 0
    assert sum(s.cache_hit_copy_bytes for s in stats["alias"]) == 0
    assert sum(s.cache_hit_copy_bytes for s in stats["copy"]) > 0


def test_windowed_arch_falls_back_to_copy(dense):
    """SWA recycles KV pages in place; alias mode must silently degrade to
    the copy path there (a shared page would be rewritten under every
    other reader)."""
    cfg = smoke_config("mixtral-8x7b")            # attn_pattern == swa
    kvcfg = make_paged_config(cfg, seq_len=128, lanes=2, page_size=8,
                              dtype=jnp.float32)
    eng = ServingEngine(cfg, kvcfg, init_params(cfg, dtype=jnp.float32),
                        dtype=jnp.float32, prefix_cache=True,
                        prefix_alias="alias")
    assert eng.prefix_alias == "alias" and not eng.alias_enabled
