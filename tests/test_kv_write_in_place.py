"""The decode step only reads the KV pool; the engine writes the step's K/V
into the donated pool in place (DESIGN.md §16).

* tokens and logits are bit-identical to one plain, undonated ``jax.jit``
  of the same ``make_decode_step`` that writes its own copy of the pool;
* every step's write consumes the pre-step pool (its arrays are deleted)
  and ``kv_writes_in_place`` counts each such step; a family with no pool
  skips the write;
* a 2-shard ``MultiEngine`` on one shared executable stays bit-identical
  to its per-shard executables, each shard writing its own pool.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.paged_kv import write_kv
from repro.models import init_params, make_paged_config
from repro.serve.engine import AdmissionItem, ServingEngine
from repro.serve.multi_engine import MultiEngine
from repro.serve.scheduler import Request, make_scheduler_config
from repro.serve.serve_step import make_decode_step

ARCH = "deepseek-7b"
STEPS = 14          # page size 4: every lane crosses 3+ page boundaries


@pytest.fixture(scope="module")
def dense():
    cfg = smoke_config(ARCH)
    return cfg, init_params(cfg, dtype=jnp.float32)


def _engine(cfg, params, lanes=4):
    kvcfg = make_paged_config(cfg, seq_len=64, lanes=lanes, page_size=4,
                              dtype=jnp.float32)
    return ServingEngine(cfg, kvcfg, params, dtype=jnp.float32)


def _admit(eng, cfg, seed=3):
    """Admit prompts of distinct lengths into every lane but the last."""
    rng = np.random.RandomState(seed)
    items = [AdmissionItem(lane, rng.randint(0, cfg.vocab_size, size=n)
                           .astype(np.int32))
             for lane, n in enumerate((5, 8, 11))]
    assert eng.admit_many(items) == []


def test_decode_matches_undonated_jit(dense):
    cfg, params = dense
    eng = _engine(cfg, params)
    _admit(eng, cfg)
    step = make_decode_step(cfg, eng.kvcfg, alloc_backend=eng.alloc_backend,
                            alloc_policy=eng.alloc_policy,
                            tenants=eng.tenants, traced_classes=True)

    @jax.jit
    def plain(params, state, class_ids):
        new, logits, _ = step(params, state, class_ids)
        k, v = write_kv(state.paged.k_pages, state.paged.v_pages,
                        new.kv_write)
        return new._replace(paged=new.paged._replace(k_pages=k, v_pages=v),
                            kv_write=None), logits

    ref = jax.tree.map(jnp.copy, eng.state)
    for _ in range(STEPS):
        logits, _ = eng.decode_step()
        ref, ref_logits = plain(params, ref, eng._class_ids)
        np.testing.assert_array_equal(np.asarray(eng.state.tokens),
                                      np.asarray(ref.tokens))
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
    for a, b in zip(jax.tree.leaves(eng.state), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", [ARCH, "rwkv6-7b"])
def test_step_consumes_the_pre_step_pool(arch):
    cfg = smoke_config(arch)
    eng = _engine(cfg, init_params(cfg, dtype=jnp.float32))
    _admit(eng, cfg)
    paged = cfg.family != "ssm"
    for _ in range(STEPS):
        k, v = eng.state.paged.k_pages, eng.state.paged.v_pages
        eng.step()
        assert k.is_deleted() == paged and v.is_deleted() == paged
        assert not eng.state.paged.k_pages.is_deleted()
    st = eng.stats
    assert st.decode_steps == STEPS
    assert st.kv_writes_in_place == (STEPS if paged else 0)


def _serve_two_shards(cfg, params, shared):
    kvcfg = make_paged_config(cfg, seq_len=64, lanes=2, page_size=4,
                              dtype=jnp.float32)
    rng = np.random.RandomState(11)
    reqs = [Request(rid=i, tokens=rng.randint(0, cfg.vocab_size, size=6 + i)
                    .astype(np.int32), max_new_tokens=10) for i in range(6)]
    me = MultiEngine(cfg, kvcfg, params, n_engines=2, dtype=jnp.float32,
                     sched_cfg=make_scheduler_config(cfg, kvcfg,
                                                     max_prompt_len=32),
                     quantum=3, shared_decode=shared)
    me.serve(reqs, max_new_tokens=10, validate=True)
    assert not me.failed
    return me, {r.rid: list(r.output) for r in reqs}


def test_two_shards_shared_matches_per_shard(dense):
    cfg, params = dense
    shared, out_shared = _serve_two_shards(cfg, params, True)
    single, out_single = _serve_two_shards(cfg, params, False)
    assert out_shared == out_single
    assert shared.stats.decode_compiles == 1
    assert single.stats.decode_compiles == 2
    for me in (shared, single):
        for eng in me.engines:
            assert eng.stats.decode_steps > 0
            assert eng.stats.kv_writes_in_place == eng.stats.decode_steps
