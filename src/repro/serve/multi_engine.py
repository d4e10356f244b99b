"""Multi-engine sharded serving on ONE shared AllocService (DESIGN.md §10).

The paper's central claim is that one lightweight support-core serves MANY
client cores' allocation traffic without cross-core metadata
synchronization.  This module is that claim at the serving layer:

* **N engine shards, one service** — each
  :class:`~repro.serve.engine.ServingEngine` registers its tenant set
  (``kv_pages`` [+ ``state_slots``] [+ ``scratch``]) under its own namespace
  (``"e0/kv_pages"``, ``"e1/kv_pages"`` ...) on ONE shared
  :class:`~repro.alloc.AllocService`, whose single
  :class:`~repro.core.freelist.FreeListState` carries every shard's
  segregated classes.  Sharding is purely a tenant-table question: quota
  isolation between shards is the same hard per-class isolation tenants
  already have, and no shard ever sees another's metadata.
* **Async decode loop with burst windows** — within a scheduling quantum of
  decode steps, each shard's deferrable allocator traffic (stash refills,
  overflow flushes, lane releases) accumulates as staged
  :class:`~repro.core.paged_kv.PendingDecodeOps` instead of committing one
  burst per engine per step; the window then drains EVERYTHING into one
  merged ``BurstBuilder`` commit.  Only on-path emergency mallocs (a lane
  whose stash pop missed at a page boundary) stay inside the per-engine
  jitted step — they gate on any-live-packet, so steady-state stash-served
  steps still cost zero central work.  Deferral never changes token output
  (pages only decide WHERE KV lands, never its values).
* **Scheduler preemption** — when a shard's pool runs dry and a
  higher-priority request is waiting, the scheduler evicts the
  lowest-priority running lane: the engine FREE_ALLs every block the lane
  owns through the builder, and the request re-queues with its generated
  prefix so a later re-admission resumes exactly where it stopped.
  Admission can therefore never deadlock behind a low-priority long tail.

The loop is host-driven like the single-engine ``serve_loop``: all device
work stays in the engines' jitted steps; the window merge runs the same
eager ``commit`` path admission and release always used.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core import paged_kv as pkv
from ..core.lane_stash import stash_push_batch
from ..core.paged_kv import PagedKVConfig
from .engine import ServingEngine, run_admission
from .router import Router, shard_load
from .scheduler import (Request, Scheduler, SchedulerConfig,
                        make_scheduler_config)


@dataclasses.dataclass
class MultiEngineStats:
    """Cross-shard telemetry of the async serving loop."""

    windows: int = 0               # burst windows driven
    window_commits: int = 0        # merged commits actually issued (gated)
    window_slots_live: int = 0     # non-NOP slots across merged commits
    window_slots_capacity: int = 0  # total slots across merged commits
    preemptions: int = 0           # lanes evicted across all shards
    decode_steps: int = 0          # engine-steps summed over shards
    # --- decode compile accounting (DESIGN.md §13) ---
    # DISTINCT decode executables built across the deployment: 1 with the
    # shared tenant-agnostic step (however many shards), N when each shard
    # is forced onto its own jit (the differential baseline).
    decode_compiles: int = 0
    decode_compile_us: float = 0.0  # trace+compile wall time, summed

    @property
    def cross_engine_burst_occupancy(self) -> float:
        """Mean fraction of merged-window HMQ slots carrying a live packet —
        how well N engines' deferred traffic packs the shared burst."""
        if not self.window_slots_capacity:
            return 0.0
        return self.window_slots_live / self.window_slots_capacity


class MultiEngine:
    """N continuous-batching engine shards multiplexed onto one support-core.

    ``quantum`` is the burst-window length in decode steps: deferrable
    allocator traffic from every shard accumulates for ``quantum`` steps and
    is then served by ONE merged commit.  ``quantum=1`` reproduces the
    per-step commit cadence (the N=1 differential-test configuration).
    """

    def __init__(self, cfg: ArchConfig, kvcfg: PagedKVConfig, params: dict,
                 n_engines: int = 2, dtype=jnp.float32,
                 sched_cfg: Optional[SchedulerConfig] = None,
                 quantum: int = 4, preemption: bool = True,
                 router: str = "round_robin",
                 alloc_backend: Optional[str] = None,
                 alloc_policy: Optional[str] = None,
                 prefix_cache: bool = False,
                 eviction: Optional[str] = None,
                 cache_pages: Optional[int] = None,
                 prefix_alias: Optional[str] = None,
                 shared_decode: bool = True):
        if n_engines < 1:
            raise ValueError("n_engines must be >= 1")
        if quantum < 1:
            raise ValueError("quantum must be >= 1")
        from ..alloc.service import AllocService
        from ..perf_flags import current_flags
        self.cfg = cfg
        self.kvcfg = kvcfg
        self.n_engines = n_engines
        self.quantum = quantum
        self.preemption = preemption
        self.alloc_backend = alloc_backend if alloc_backend is not None \
            else current_flags().alloc_backend
        self.alloc_policy = alloc_policy if alloc_policy is not None \
            else current_flags().alloc_policy

        # ONE service, N namespaced tenant sets, ONE shared freelist state
        # covering every shard's classes (registration before init_state —
        # the service guards against later growth).
        self.service = AllocService(policy=self.alloc_policy,
                                    backend=self.alloc_backend)
        tenant_sets = [pkv.register_paged_tenants(self.service, kvcfg,
                                                  namespace=f"e{i}")
                       for i in range(n_engines)]
        self.alloc = self.service.init_state()

        # ONE decode executable for all N shards (DESIGN.md §13): the step
        # is tenant-agnostic — each shard passes its namespaced class ids
        # as a traced operand — so every shard can drive the SAME jitted
        # callable and the deployment pays exactly one XLA compile (like
        # the shared prefill cache below).  ``shared_decode=False`` forces
        # the historical per-shard jit objects (N identical compiles) —
        # the differential baseline the shared-executable tests diff
        # against for bit-identical tokens.
        shared_fn = None
        if shared_decode:
            from .serve_step import CountingJit, make_decode_step
            shared_fn = CountingJit(make_decode_step(
                cfg, kvcfg, alloc_backend=self.alloc_backend,
                alloc_policy=self.alloc_policy, tenants=tenant_sets[0],
                defer_refill=True, traced_classes=True))

        scfg = sched_cfg or make_scheduler_config(cfg, kvcfg)
        self.engines = [
            ServingEngine(cfg, kvcfg, params, dtype=dtype, sched_cfg=scfg,
                          alloc_backend=self.alloc_backend,
                          alloc_policy=self.alloc_policy,
                          tenants=ts, alloc_state=self.alloc,
                          defer_refill=True,
                          # per-shard caches: each shard demotes/probes only
                          # its own namespaced KV class, so caches need no
                          # cross-shard coordination (DESIGN.md §11)
                          prefix_cache=prefix_cache, eviction=eviction,
                          cache_pages=cache_pages,
                          prefix_alias=prefix_alias,
                          decode_fn=shared_fn)
            for ts in tenant_sets]
        # the prefill is allocator-free and identical across shards: share
        # the jit cache so N shards pay ONE compile per prefill bucket
        for eng in self.engines[1:]:
            eng._prefill_cache = self.engines[0]._prefill_cache
        self.scheds = [Scheduler(scfg) for _ in range(n_engines)]
        self.router = Router(router)
        self.stats = MultiEngineStats()

    # ---------------- shared-allocator threading ----------------

    def _sync(self, i: int) -> ServingEngine:
        """Install the authoritative shared freelist into shard i's state."""
        eng = self.engines[i]
        if eng.state.paged.alloc is not self.alloc:
            eng.state = eng.state._replace(
                paged=eng.state.paged._replace(alloc=self.alloc))
        return eng

    def _pull(self, i: int) -> None:
        """Adopt shard i's post-op freelist as the authoritative one."""
        self.alloc = self.engines[i].state.paged.alloc

    # ---------------- intake ----------------

    def submit(self, requests: Sequence[Request],
               max_new_tokens: Optional[int] = None) -> list[int]:
        """Route requests onto shards; returns the shard index per request."""
        shards = []
        for req in requests:
            if max_new_tokens is not None:
                req.max_new_tokens = max_new_tokens
            shard = self.router.route([shard_load(s) for s in self.scheds])
            self.scheds[shard].submit(req)
            shards.append(shard)
        return shards

    @property
    def has_work(self) -> bool:
        return any(s.has_work for s in self.scheds)

    # ---------------- the async serving loop ----------------

    def serve(self, requests: Sequence[Request], max_new_tokens: int = 16,
              validate: bool = False, verbose: bool = False) -> int:
        """Drive every request to completion; returns total burst windows.

        ``validate`` runs the full shared-state invariant check (I1–I4 over
        every shard's classes + per-shard I5 stash partition) after every
        burst window — the multi-tenant isolation proof, test-only cost.
        """
        self.submit(requests, max_new_tokens=max_new_tokens)
        windows = 0
        while self.has_work:
            progressed = self.step_window(validate=validate)
            windows += 1
            if verbose:
                done = sum(len(s.finished) for s in self.scheds)
                print(f"window {windows}: done={done}/{len(requests)} "
                      f"commits={self.stats.window_commits} "
                      f"preemptions={self.stats.preemptions}")
            if not progressed:
                stranded = sum(len(s.waiting) for s in self.scheds)
                print(f"WARNING: multi-engine admission starved — "
                      f"{stranded} request(s) not served")
                break
        return windows

    def step_window(self, validate: bool = False) -> bool:
        """One burst window: admission (+preemption), a quantum of decode
        steps on every shard, then ONE merged window commit.  Returns
        whether any shard made progress (admitted or decoded)."""
        progressed = False
        # --- admission + preemption phase (one admission burst per shard:
        # prefill compute and the KV install are inherently per-shard; the
        # lifecycle block itself is the same one serve_loop runs)
        for i, sched in enumerate(self.scheds):
            eng = self._sync(i)
            if not sched.waiting:
                continue
            if run_admission(eng, sched, preemption=self.preemption,
                             after_op=lambda i=i: self._pull(i)):
                progressed = True
        self.stats.preemptions = sum(e.stats.preemptions
                                     for e in self.engines)

        # --- decode quantum: engines step round-robin; deferrable allocator
        # ops pile up in each engine's pending_ops, releases in `released`,
        # prefix-cache eviction victims in `evicted` (freed at the window
        # commit, like everything else deferrable)
        released: list[list[int]] = [[] for _ in self.engines]
        evicted: list[list[int]] = [[] for _ in self.engines]
        for _ in range(self.quantum):
            for i, sched in enumerate(self.scheds):
                if not sched.running:
                    continue
                eng = self._sync(i)
                tokens = eng.step()
                self._pull(i)
                self.stats.decode_steps += 1
                progressed = True
                finished = sched.note_decode_step(tokens)
                if finished:
                    if eng.cache is not None:
                        # demote full KV pages into the shard's prefix
                        # cache BEFORE the block-table rows clear and
                        # BEFORE the window's FREE_ALLs commit: kept pages
                        # retag to CACHE_OWNER on the SHARED freelist (pull
                        # it), victims ride the window commit as frees
                        evicted[i].extend(eng._demote_lanes(
                            {l: sched.kv_token_prefix(l) for l in finished}))
                        self._pull(i)
                        # alias mode: drop the finished lanes' pins on
                        # shared prefix pages AFTER demote (pins shield the
                        # insert's budget evictions); the per-lane refcount
                        # decrements ride the window commit as singles,
                        # exactly like the eviction victims
                        evicted[i].extend(eng._unalias_lanes(finished))
                        eng._sync_cache_stats()
                    # host metadata clears now; the FREE_ALL packets ride
                    # the merged window commit below
                    mask = np.zeros((self.kvcfg.max_lanes,), bool)
                    mask[finished] = True
                    eng.state = eng.state._replace(
                        paged=pkv.clear_released_lanes(
                            eng.state.paged, jnp.asarray(mask)))
                    eng.stats.completed += len(finished)
                    released[i].extend(finished)
                    sched.complete(finished)

        self._flush_window(released, evicted)
        if self.service.recorder is not None:
            # window boundary marker in the allocator-op trace — replay
            # analysis buckets traffic per burst window (DESIGN.md §14)
            self.service.recorder.mark_window()
        self.stats.windows += 1
        self._sync_compile_stats()
        if validate:
            self.validate()
        return progressed

    def _sync_compile_stats(self) -> None:
        """Fold decode compile accounting into the cross-shard stats.

        Counts DISTINCT executables: with the shared tenant-agnostic step
        every shard holds the same CountingJit, so N shards contribute its
        counter once (== 1); the forced per-shard mode sums N private
        jits' counters (== N).  Same dedup for the compile wall time."""
        distinct = {id(e._decode): e._decode for e in self.engines}
        self.stats.decode_compiles = sum(
            j.compiles for j in distinct.values())
        self.stats.decode_compile_us = sum(
            j.compile_us for j in distinct.values())

    def _flush_window(self, released: list[list[int]],
                      evicted: Optional[list[list[int]]] = None) -> None:
        """ONE merged commit for every shard's deferred window traffic:
        stash refills (OR of the below-watermark masks over the quantum),
        overflow flushes, completed-lane FREE_ALLs, and prefix-cache
        eviction victims (single owner-agnostic frees — the FREE_ALLs skip
        CACHE_OWNER pages, so demoted survivors stay resident)."""
        L = self.kvcfg.max_lanes
        S = self.kvcfg.stash_size
        lane_ids = jnp.arange(L, dtype=jnp.int32)
        burst = self.service.new_burst()
        installs = []                      # (shard, ticket, below_mask)
        for i, eng in enumerate(self.engines):
            pend, eng.pending_ops = eng.pending_ops, []
            active = eng.state.paged.active
            if pend and S:
                below = pend[0].below
                for p in pend[1:]:
                    below = below | p.below
                # lanes released (or evicted) after wanting a refill must
                # not have pages pushed into their cleared stash rows, and
                # a stash that recovered via recycle pushes since it dipped
                # must still have room for the all-or-nothing refill batch
                below = below & active & (eng.state.paged.stash.depth
                                          <= S - self.kvcfg.stash_refill)
                t = burst.refill(eng.tenants.kv, lane_ids,
                                 self.kvcfg.stash_refill, where=below)
                installs.append((i, t, below))
            if eng.window is not None:
                # overflow flushes exist only under SWA page recycling:
                # skipping the staging entirely for windowless archs keeps
                # engines*quantum*max_lanes guaranteed-NOP slots out of the
                # merged burst (they would dilute its occupancy metric)
                for p in pend:
                    # builder.free() NOPs NO_BLOCK entries; a flushed block
                    # of a since-released lane dedups against its FREE_ALL
                    # (the free mask is an owner-map union — frees once,
                    # never twice)
                    burst.free(eng.tenants.kv, lane_ids, p.flush_blocks,
                               where=p.flush_mask)
            if released[i]:
                valid = np.zeros((L,), bool)
                valid[released[i]] = True
                pkv.stage_release_ops(eng.tenants, burst, lane_ids,
                                      jnp.asarray(valid))
            if evicted is not None and evicted[i]:
                blocks = jnp.asarray(evicted[i], jnp.int32)
                burst.free(eng.tenants.kv,
                           jnp.zeros((blocks.shape[0],), jnp.int32), blocks)
        if not burst.size:
            return
        self.alloc, res = self.service.commit(
            self.alloc, burst,
            max_blocks_per_req=max(1, self.kvcfg.stash_refill if S else 1),
            backend=self.alloc_backend, policy=self.alloc_policy, gated=True)
        # install refill grants into each shard's stash
        for i, t, below in installs:
            eng = self._sync(i)
            got = res.ok_for(t) & below
            stash = stash_push_batch(eng.state.paged.stash,
                                     res.blocks_for(t)[:, :self.kvcfg.stash_refill],
                                     self.kvcfg.stash_refill, got)
            eng.state = eng.state._replace(
                paged=eng.state.paged._replace(stash=stash))
        # fold the merged burst into per-shard telemetry (each shard sees
        # its own tenants' rows) and the window occupancy into ours
        live = bool(int(res.live))
        self.stats.window_commits += int(live)
        if live:
            self.stats.window_slots_live += int(res.stats.queue_live)
            self.stats.window_slots_capacity += int(res.stats.queue_capacity)
        for eng in self.engines:
            eng._note_burst(res.stats.per_tenant, issued=False)

    # ---------------- reporting / validation ----------------

    def validate(self) -> None:
        """Full shared-allocator invariant check: I1–I4 across EVERY
        shard's classes, plus each shard's I5 stash/block-table partition
        against its own KV class (raises FreelistInvariantError)."""
        for i, eng in enumerate(self.engines):
            self._sync(i)
            pkv.validate_paged_kv(self.kvcfg, eng.state.paged,
                                  tenants=eng.tenants, cache=eng.cache)

    @property
    def finished(self) -> list[Request]:
        return [r for s in self.scheds for r in s.finished]

    @property
    def failed(self) -> list[Request]:
        return [r for s in self.scheds for r in s.failed]

    def tenant_rollup(self) -> dict[str, dict]:
        """Cross-engine per-tenant rollup of the shared allocator state."""
        return self.service.rollup_report(self.alloc)
