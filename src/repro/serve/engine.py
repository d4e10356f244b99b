"""Serving engine: scheduler-driven continuous batching on the SpeedMalloc
paged KV (DESIGN.md §3).

Host-side orchestration around the jitted prefill/decode steps.  Admission is
*batched*: the scheduler hands the engine a batch of k sequences, the engine
runs ONE jitted bucketed prefill per prompt bucket (compile once per bucket,
not once per prompt length) and installs the whole batch's KV through ONE
support-core HMQ burst (`paged_kv.admit_prefill_many`) — the paper's batched
"server-client" (Larson) admission.  Completion releases lanes through
OP_FREE/FREE_ALL request packets, so the engine's entire allocation
lifecycle — admit, per-step append, release — speaks the packet protocol.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ArchConfig
from ..core import paged_kv as pkv
from ..core.paged_kv import PagedKVConfig
from ..models import decode as dec
from .scheduler import (SchedulerConfig, make_scheduler_config, pick_bucket,
                        release_packet_array)
from .serve_step import (CountingJit, ServeState, init_serve_state,
                         make_decode_step, make_family_prefill,
                         recycle_window, write_kv_in_place)

#: JAX's event around every executable it compiles or loads from the
#: persistent compilation cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HOST_READS = {"repro.admission": "admission_host_reads",
               "repro.release": "release_host_reads",
               "repro.decode": "decode_host_reads"}
_COMPILES = {"repro.admission": "admission_compiles",
             "repro.release": "release_compiles"}
_listening = False


def _stage(name: str) -> str:
    """``repro.admission.plan`` -> ``repro.admission``."""
    return ".".join(name.split(".", 2)[:2])


def _count_compile(event: str, duration: float, **kw) -> None:
    """Credit a compile to the owner of the innermost ``repro.*`` span."""
    top = obs.innermost()
    if event != _COMPILE_EVENT or top is None or top[1] is None:
        return
    field = _COMPILES.get(_stage(top[0]))
    if field is not None:
        setattr(top[1], field, getattr(top[1], field) + 1)


def _listen_for_compiles() -> None:
    """Register ``_count_compile`` once per process (JAX listeners cannot
    be told apart once registered)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        _listening = True


@dataclasses.dataclass
class EngineStats:
    admitted: int = 0
    completed: int = 0
    decode_steps: int = 0
    kv_writes_in_place: int = 0    # decode steps whose K/V write consumed
    #                                the donated pool (DESIGN.md §16)
    preemptions: int = 0           # running lanes evicted by the scheduler
    alloc_failures: int = 0        # failed malloc packets (all families)
    hmq_admit_bursts: int = 0      # support-core steps issued for admission
    hmq_release_bursts: int = 0    # eager release/eviction bursts issued
    prefill_compiles: int = 0      # distinct prefill buckets compiled
    # --- decode compile accounting (DESIGN.md §13) ---
    # With traced class ids the decode executable is tenant-agnostic, so N
    # shards sharing one jitted step report decode_compiles == 1 (each
    # shard mirrors the SHARED executable's counter — not a per-shard sum).
    decode_compiles: int = 0       # decode executables built (trace events)
    decode_compile_us: float = 0.0  # trace+compile wall time of those builds
    # --- stash front-end telemetry (DESIGN.md §7) ---
    decode_bursts: int = 0         # decode steps that issued a support-core batch
    stash_hits: int = 0            # boundary pages served by the lane stash
    stash_misses: int = 0          # boundary pages that needed a central malloc
    # stash_depth_hist[d] = lane-steps an active lane spent at stash depth d
    # (summed per-step histograms; localizes refill storms — DecodeStats)
    stash_depth_hist: list = dataclasses.field(default_factory=list)
    # --- multi-tenant telemetry (DESIGN.md §9) ---
    # tenants[name] = cumulative mallocs/failed/blocks_allocated/blocks_freed
    # plus the latest occupancy ("used") and the static quota, accumulated
    # from every burst's per-tenant StepStats breakdown.
    tenants: dict = dataclasses.field(default_factory=dict)
    burst_slots_live: int = 0      # non-NOP slots across all issued bursts
    burst_slots_capacity: int = 0  # total slots across all issued bursts
    # --- prefix-cache telemetry (DESIGN.md §11) ---
    cache_hits: int = 0            # admissions that reused >= 1 cached page
    cache_misses: int = 0          # probed admissions with no cached prefix
    cache_inserts: int = 0         # pages demoted into the cache
    cache_evictions: int = 0       # pages evicted from the cache
    cache_pages: int = 0           # pages the cache holds right now
    prefill_tokens_saved: int = 0  # prompt tokens skipped via cached pages
    # --- zero-copy hit admission (DESIGN.md §12) ---
    aliased_pages: int = 0         # cache pages spliced into lane block tables
    cache_hit_copy_bytes: int = 0  # prefix K/V bytes copied into fresh lane
    #                                pages at hit admission (0 in alias mode)
    cache_hit_admits: int = 0      # admission batches containing >= 1 hit
    cache_hit_admit_us: float = 0.0  # wall time spent in those batches
    # --- contiguity + fragmentation telemetry (DESIGN.md §15) ---
    # Folded at admission over just-admitted lanes' block-table rows: an
    # extent is a maximal run of CONSECUTIVE page ids, so
    # extent_pages / contiguous_extents is the mean granted run length —
    # 1.0 under freelist/bitmap churn, > 1 when the buddy policy serves
    # admission's OP_MALLOC_RUN packets contiguously.
    contiguous_extents: int = 0    # maximal consecutive-id runs admitted
    extent_pages: int = 0          # pages covered by those runs
    compactions: int = 0           # between-window compaction passes run
    compaction_moves: int = 0      # pages migrated by those passes
    # --- host work per lifecycle stage (DESIGN.md §16) ---
    # blocking device->host reads (``ServingEngine._fetch``) and executables
    # compiled or loaded from the persistent cache, each credited to the
    # stage whose ``repro.*`` span is innermost when it happens
    decode_host_reads: int = 0
    admission_host_reads: int = 0
    release_host_reads: int = 0
    admission_compiles: int = 0
    release_compiles: int = 0

    @property
    def hit_admit_us(self) -> float:
        """Mean wall-clock microseconds per admission batch that contained
        at least one prefix-cache hit — the copy-vs-alias speedup metric."""
        if not self.cache_hit_admits:
            return 0.0
        return self.cache_hit_admit_us / self.cache_hit_admits

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of admission-time probes that found a reusable cached
        prefix (0.0 with the cache off)."""
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def mean_run_len(self) -> float:
        """Mean contiguous-run length of admitted KV pages (pages per
        extent; 1.0 == every page an island)."""
        if not self.contiguous_extents:
            return 0.0
        return self.extent_pages / self.contiguous_extents

    @property
    def stash_hit_rate(self) -> float:
        """Fraction of page-boundary allocations the stash front-end served."""
        total = self.stash_hits + self.stash_misses
        return self.stash_hits / total if total else 0.0

    @property
    def hmq_bursts_per_1k_decode_steps(self) -> float:
        """Central-allocator bursts per 1000 decode steps (pre-stash
        baseline: 1000 — one support-core batch every step)."""
        if not self.decode_steps:
            return 0.0
        return 1000.0 * self.decode_bursts / self.decode_steps

    @property
    def burst_occupancy(self) -> float:
        """Mean fraction of HMQ slots carrying a live packet per issued
        burst — how well multi-tenant traffic packs the fixed-capacity
        queue."""
        if not self.burst_slots_capacity:
            return 0.0
        return self.burst_slots_live / self.burst_slots_capacity


class AdmissionItem(NamedTuple):
    """One sequence the scheduler asks the engine to install."""

    lane: int
    tokens: np.ndarray                    # [T] int32
    frames: Optional[np.ndarray] = None   # [F, d] (audio)
    patches: Optional[np.ndarray] = None  # [P, d] (vlm)
    cached_len: int = 0                   # prefix tokens served by the cache


def run_admission(eng: "ServingEngine", sched, preemption: bool = False,
                  after_op=None) -> bool:
    """One admission pass of the serving lifecycle, shared by the
    single-engine ``serve_loop`` and ``MultiEngine.step_window``.

    Plans under the page budget, optionally evicts a lower-priority running
    lane when admission is stuck (strict priority preemption — DESIGN.md
    §10), admits the batch, records the admission-seeded first generated
    tokens (``Scheduler.note_admission``), and retires requests the seed
    already finished.  ``after_op`` runs after every engine-side allocator
    op (the multi-engine loop passes its shared-freelist ``_pull``).
    Returns whether anything was admitted.

    With the prefix cache on (DESIGN.md §11), planning probes the cache so
    each candidate is bucketed by its UNCACHED suffix, and a stuck
    admission first evicts cold cached pages (strictly lower priority than
    running lanes) before resorting to preemption.  Requests the admission
    seed finishes are demoted back into the cache on release.
    """
    with obs.span("repro.admission", owner=eng.stats) as sp:
        return _admission_pass(eng, sched, sp, preemption, after_op)


def _admission_pass(eng: "ServingEngine", sched, sp, preemption: bool,
                    after_op) -> bool:
    sync = after_op if after_op is not None else (lambda: None)
    probe = eng.cache_probe if eng.cache is not None else None
    alias = eng.alias_enabled

    def plan_pass():
        with obs.span("repro.admission.plan", owner=eng.stats):
            return sched.plan_admission(eng.free_pages, probe=probe,
                                        alias=alias)

    plan = plan_pass()
    if not plan.size and eng.cache is not None and eng.cache.pages:
        short = sched.head_shortfall(eng.free_pages)
        if short is not None and eng.cache_release(short):
            sync()
            # evicting may have shortened the head's cached prefix — replan
            # so cached_len/bucket/page math all reflect the new cache state
            plan = plan_pass()
    if not plan.size and preemption:
        lane = sched.preempt_victim(free_pages=eng.free_pages)
        if lane is not None:
            # FREE_ALL through the builder, immediately: the admission
            # this eviction unblocks happens in this very pass
            eng.preempt([lane])
            sync()
            sched.preempt(lane)
            plan = plan_pass()
    if not plan.size:
        return False
    items = [AdmissionItem(lane, r.tokens, r.frames, r.patches, r.cached_len)
             for b in plan.batches for lane, r in b.items]
    sp.set(lanes=[it.lane for it in items],
           rids=[r.rid for b in plan.batches for _, r in b.items])
    failed = eng.admit_many(items)      # failed lanes come back reclaimed
    sync()
    sched.commit_admission(plan)
    if failed:
        sched.fail_admission(failed)
        print(f"WARNING: allocator rejected admission of "
              f"{len(failed)} request(s) (pool exhausted)")
    # the admission seed is the first generated token (attention
    # families): record it, and retire max_new_tokens==1 requests
    done0 = sched.note_admission(eng.admitted_tokens)
    if done0:
        kv_toks = {l: sched.kv_token_prefix(l) for l in done0} \
            if eng.cache is not None else None
        eng.release(done0, kv_tokens=kv_toks)
        sync()
        sched.complete(done0)
    return True


class ServingEngine:
    """Continuous-batching engine.  Lanes = slots in the running batch."""

    def __init__(self, cfg: ArchConfig, kvcfg: PagedKVConfig, params: dict,
                 dtype=jnp.float32,
                 sched_cfg: Optional[SchedulerConfig] = None,
                 alloc_backend: Optional[str] = None,
                 alloc_policy: Optional[str] = None,
                 tenants: Optional[pkv.PagedTenants] = None,
                 alloc_state=None,
                 defer_refill: bool = False,
                 prefix_cache: bool = False,
                 eviction: Optional[str] = None,
                 cache_pages: Optional[int] = None,
                 prefix_alias: Optional[str] = None,
                 decode_fn=None):
        self.cfg = cfg
        self.kvcfg = kvcfg
        self.params = params
        self.dtype = dtype
        self.sched_cfg = sched_cfg or make_scheduler_config(cfg, kvcfg)
        # Support-core implementation for every allocator touch this engine
        # makes (admission, decode burst, release): jnp | kernel |
        # kernel-interpret backend, and the freelist | bitmap policy.
        # Resolved ONCE here (env knobs REPRO_ALLOC_BACKEND /
        # REPRO_ALLOC_POLICY) so the jitted decode step bakes them in.
        from ..perf_flags import current_flags
        if alloc_backend is None:
            alloc_backend = current_flags().alloc_backend
        if alloc_policy is None:
            alloc_policy = current_flags().alloc_policy
        self.alloc_backend = alloc_backend
        self.alloc_policy = alloc_policy
        # The support-core's client API handle: this engine's tenant set
        # (kv_pages [+ state_slots] [+ scratch]) and per-tenant reporting.
        # ``tenants`` installs a NAMESPACED set on a SHARED multi-engine
        # service (DESIGN.md §10); the default is the per-config service.
        self.tenants = tenants if tenants is not None \
            else pkv.paged_tenants(kvcfg)
        self.service = self.tenants.service
        # ``defer_refill``: the multi-engine async loop's burst-window mode —
        # the decode step returns deferrable refill/flush ops (accumulated in
        # ``pending_ops``) instead of committing them per step.
        self.defer_refill = defer_refill
        self.pending_ops: list = []
        # Prefix cache (DESIGN.md §11): completed lanes' full KV pages
        # survive as CACHE_OWNER-retagged blocks, probed at admission for
        # prefill skip.  Off by default — the legacy lifecycle is exactly
        # unchanged when ``self.cache is None``.
        self.cache: Optional[pkv.PrefixCache] = None
        if prefix_cache:
            from ..alloc.eviction import get_eviction
            budget = cache_pages if cache_pages is not None \
                else kvcfg.num_pages // 2
            self.cache = pkv.PrefixCache(kvcfg.page_size, budget,
                                         policy=get_eviction(eviction))
        # Hit-admission mode (DESIGN.md §12): "copy" gathers cached K/V into
        # freshly allocated lane pages; "alias" splices the cache-owned page
        # ids into the lane's block table with a refcount bump — zero copy.
        # Resolved once (env knob REPRO_PREFIX_ALIAS) like the backend/policy.
        if prefix_alias is None:
            prefix_alias = current_flags().prefix_alias
        if prefix_alias not in ("copy", "alias"):
            raise ValueError(
                f"prefix_alias must be 'copy' or 'alias', got {prefix_alias!r}")
        self.prefix_alias = prefix_alias
        # lane -> (pinned token prefix, shared block ids): the lanes whose
        # block tables currently reference cache-owned pages; release must
        # drop the pins and single-OP_FREE the per-lane refcounts
        self._aliased: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self.admitted_tokens: dict[int, int] = {}
        self.state = init_serve_state(cfg, kvcfg, kvcfg.max_lanes, 0, dtype)
        # fresh empty state: deactivate the synthetic lanes (metadata
        # initialized by the SAME policy the engine's bursts will run);
        # ``alloc_state`` threads in the one shared multi-engine freelist.
        self.state = self.state._replace(
            paged=pkv.init_paged_kv(kvcfg, policy=alloc_policy,
                                    alloc=alloc_state, tenants=self.tenants),
            tokens=jnp.zeros((kvcfg.max_lanes,), jnp.int32))
        # The decode step is TENANT-AGNOSTIC (DESIGN.md §13): this shard's
        # namespaced class ids travel as a traced int32 operand per call,
        # never as trace-time constants — so identical-config shards produce
        # identical traces.  ``decode_fn`` installs a SHARED CountingJit
        # (the multi-engine path: N shards, ONE executable, one compile);
        # the default builds a private one (decode_compiles == 1 either way).
        self._class_ids = self.tenants.class_id_array()
        if decode_fn is not None:
            self._decode = decode_fn
        else:
            self._decode = CountingJit(make_decode_step(
                cfg, kvcfg, alloc_backend=alloc_backend,
                alloc_policy=alloc_policy, tenants=self.tenants,
                defer_refill=defer_refill, traced_classes=True))
        # recurrent admission seeds decode from the last prompt token, so the
        # vocab projection would be dead weight in the jitted prefill
        self._family_prefill = make_family_prefill(
            cfg, recurrent_logits=cfg.family not in ("ssm", "hybrid"))
        self._prefill_cache: dict[tuple, Any] = {}
        self.stats = EngineStats()
        self.window = recycle_window(cfg)
        _listen_for_compiles()

    # ---------------- multi-tenant telemetry ----------------

    def _fetch(self, value):
        """One blocking device->host read of ``value``: an array, or a tuple
        of pytrees read in one transfer.  Counted in
        ``stats.<stage>_host_reads`` for the stage whose ``repro.*`` span
        is innermost (none outside the engine's spans)."""
        top = obs.innermost()
        field = _HOST_READS.get(_stage(top[0])) if top is not None else None
        if field is not None:
            setattr(self.stats, field, getattr(self.stats, field) + 1)
        if isinstance(value, tuple):
            return jax.device_get(value)
        return np.asarray(value)

    def _note_burst(self, per_tenant, queue_live=None, queue_capacity=None,
                    issued: bool = True) -> None:
        """Fold one burst's per-tenant StepStats breakdown (and its slot
        occupancy, when the burst was actually issued) into EngineStats."""
        # one device->host transfer for everything, not one blocking scalar
        # fetch per (field, tenant) — this runs every decode step
        self._fold_burst(*self._fetch((per_tenant, queue_live,
                                       queue_capacity)), issued=issued)

    def _fold_burst(self, pt, queue_live, queue_capacity,
                    issued: bool = True) -> None:
        """``_note_burst`` on values already on the host."""
        for t in self.tenants.handles:
            d = self.stats.tenants.setdefault(t.name, {
                "mallocs": 0, "failed": 0, "blocks_allocated": 0,
                "blocks_freed": 0, "used": 0, "quota": t.quota,
            })
            c = t.size_class
            d["mallocs"] += int(pt.mallocs[c])
            d["failed"] += int(pt.failed[c])
            d["blocks_allocated"] += int(pt.blocks_allocated[c])
            d["blocks_freed"] += int(pt.blocks_freed[c])
            d["used"] = int(pt.used[c])
        if issued and queue_live is not None:
            self.stats.burst_slots_live += int(queue_live)
            self.stats.burst_slots_capacity += int(queue_capacity)

    def tenant_report(self) -> dict[str, dict]:
        """Current per-tenant occupancy/quota/counters from the live
        allocator state (service-level snapshot; telemetry + debugging).
        Restricted to THIS engine's tenant set — on a shared multi-engine
        service the other shards' tenants never leak into the report."""
        return self.service.tenant_report(self.state.paged.alloc,
                                          tenants=self.tenants.handles)

    def fragmentation_report(self) -> dict[str, dict]:
        """Per-tenant external-fragmentation snapshot of the live allocator
        state (free pages, largest contiguous/aligned free run,
        ``external_frag``, buddy split/merge counters — DESIGN.md §15).
        Same tenant-subset convention as :meth:`tenant_report`."""
        return self.service.fragmentation_report(self.state.paged.alloc,
                                                 tenants=self.tenants.handles)

    def compact(self, max_moves: Optional[int] = None) -> int:
        """Run one between-burst-window KV compaction pass
        (:func:`repro.core.paged_kv.compact_kv`): migrate sole-owner lane
        pages into lower free holes so the free tail becomes contiguous
        again.  Aliased prefix pages, cache residents, and stash pages
        never move.  Call it between windows — never mid-burst.  Returns
        the number of pages migrated."""
        paged, moved = pkv.compact_kv(self.kvcfg, self.state.paged,
                                      tenants=self.tenants,
                                      max_moves=max_moves)
        if moved:
            self.state = self.state._replace(paged=paged)
        self.stats.compactions += 1
        self.stats.compaction_moves += moved
        return moved

    # ---------------- prefix cache (DESIGN.md §11) ----------------

    @property
    def alias_enabled(self) -> bool:
        """Zero-copy hit admission is live: alias mode selected, the cache
        on, and full attention.  Windowed archs (SWA / local_global)
        recycle KV pages in place as the window slides, which would rewrite
        a shared page under every other reader — they silently fall back
        to the copy path (DESIGN.md §12)."""
        return (self.prefix_alias == "alias" and self.cache is not None
                and self.cfg.attn_pattern == "full")

    def _unalias_lanes(self, lanes: Sequence[int]) -> list[int]:
        """Drop the released lanes' references on shared (aliased) prefix
        pages: unpin the cache entries and return the block ids, which the
        caller MUST ride as single OP_FREEs on its release burst — the
        lanes' FREE_ALLs match on owner and therefore skip these
        CACHE_OWNER pages, so without the singles the per-lane refcounts
        would leak and the pages could never return to the pool."""
        blocks: list[int] = []
        for lane in lanes:
            rec = self._aliased.pop(int(lane), None)
            if rec is None:
                continue
            toks, blks = rec
            self.cache.unalias(toks, len(blks))
            blocks.extend(int(b) for b in blks)
        return blocks

    def _sync_cache_stats(self) -> None:
        """Mirror the cache's cumulative counters into EngineStats."""
        if self.cache is None:
            return
        self.stats.cache_hits = self.cache.hits
        self.stats.cache_misses = self.cache.misses
        self.stats.cache_inserts = self.cache.inserts
        self.stats.cache_evictions = self.cache.evictions
        self.stats.cache_pages = self.cache.pages

    def cache_probe(self, req) -> int:
        """Plan-time peek: longest cached prefix (tokens) of the request's
        resume prompt; 0 when the request can't ride the cache.  No side
        effects — ``Scheduler.plan_admission`` may call this several times
        per admission pass; the admit-time ``touch=True`` lookup in
        :meth:`admit_many` does the recency/counter bookkeeping."""
        if self.cache is None or self.cfg.family in ("ssm", "hybrid"):
            return 0
        if getattr(req, "frames", None) is not None or \
                getattr(req, "patches", None) is not None:
            return 0
        n, _ = self.cache.probe(np.asarray(req.tokens, np.int32))
        return n

    def cache_release(self, n_pages: int) -> int:
        """Evict at least ``n_pages`` from the prefix cache and free them
        immediately (single OP_FREEs, one burst) — the admission-shortfall
        path.  Returns how many pages were actually freed."""
        blocks = self.cache.evict_pages(n_pages)
        if blocks:
            with obs.span("repro.release", owner=self.stats,
                          evicted=len(blocks)):
                with obs.span("repro.release.burst", owner=self.stats):
                    pkts = release_packet_array([], self.kvcfg.max_lanes)
                    paged, stats = pkv.release_packets(
                        self.kvcfg, self.state.paged, jnp.asarray(pkts),
                        backend=self.alloc_backend, policy=self.alloc_policy,
                        tenants=self.tenants, extra_free=blocks)
                    self.stats.hmq_release_bursts += 1
                with obs.span("repro.release.telemetry", owner=self.stats):
                    self._note_burst(stats.per_tenant, stats.queue_live,
                                     stats.queue_capacity)
                    self.state = self.state._replace(paged=paged)
                    self._sync_cache_stats()
        return len(blocks)

    def _demote_lanes(self, kv_tokens: dict) -> list[int]:
        """Demote completing lanes' full KV pages into the prefix cache.

        ``kv_tokens[lane]`` is the token sequence whose KV the lane holds
        (``Scheduler.kv_token_prefix``).  Pure control plane: pages the
        cache keeps are owner-retagged to :data:`~repro.core.paged_kv
        .CACHE_OWNER` so the lane's FREE_ALL leaves them resident;
        duplicates stay lane-owned for that sweep; policy victims are
        returned for the caller to ride as single frees on the release
        burst.  MUST run before the release commit.
        """
        ps = self.kvcfg.page_size
        tbl = self._fetch(self.state.paged.block_tables)
        retag: list[int] = []
        evicted: list[int] = []
        for lane, toks in kv_tokens.items():
            toks = np.asarray(toks, np.int32)
            n = len(toks) // ps
            if not n:
                continue
            blocks = tbl[lane, :n]
            if (blocks < 0).any():       # hole in the table: don't demote
                continue
            kept, _skipped, ev = self.cache.insert(toks[: n * ps], blocks)
            retag.extend(kept)
            evicted.extend(ev)
        if retag:
            alloc = self.service.retag_blocks(
                self.state.paged.alloc, self.tenants.kv,
                np.asarray(retag, np.int32), pkv.CACHE_OWNER)
            self.state = self.state._replace(
                paged=self.state.paged._replace(alloc=alloc))
        return evicted

    # ---------------- admission ----------------

    def _prefill_fn(self, group_key: tuple):
        """Jitted bucketed prefill, one compile per (bucket, aux-shape) group."""
        fn = self._prefill_cache.get(group_key)
        if fn is None:
            fn = jax.jit(self._family_prefill)
            self._prefill_cache[group_key] = fn
            self.stats.prefill_compiles += 1
        return fn

    def _group_key(self, item: AdmissionItem, bucket: int) -> tuple:
        p = item.patches.shape[0] if item.patches is not None else 0
        return (bucket, p, item.cached_len)

    def admit_many(self, items: Sequence[AdmissionItem]) -> list[int]:
        """Prefill and install a batch of sequences.

        One jitted prefill per bucket (each padded to the static
        ``admit_width`` batch rows) and — for families with paged KV — ONE
        support-core HMQ burst covering every sequence in ``items``.  Lanes
        must be distinct; the burst is issued in ascending-lane order (the
        final argsort below), so the allocator serves it bit-identically to
        sequential admission.

        Returns the lanes whose admission FAILED (allocator could not serve
        their packets).  Failed lanes are already reclaimed — any partially
        granted blocks are freed before returning, so the pool is never
        leaked — and do not count toward ``stats.admitted``; the caller only
        needs to requeue or fail the corresponding requests.

        Side channel: ``self.admitted_tokens`` maps each successfully
        admitted lane to the token the admission SEEDED decode with.  For
        attention families that seed is the argmax over the prefill's last
        position — i.e. the request's FIRST GENERATED token — and callers
        must record it as output (``Scheduler.note_admission``) or a
        preempted request's resume prefix would silently lose one token.
        Recurrent families (ssm, hybrid) seed from the last PROMPT token,
        which is not output; they publish an empty mapping.
        """
        if not items:
            return []
        import time
        t_admit0 = time.perf_counter()
        items = [it if isinstance(it, AdmissionItem) else AdmissionItem(*it)
                 for it in items]
        scfg = self.sched_cfg
        cfg = self.cfg
        W = scfg.admit_width
        alias = self.alias_enabled
        # lane -> (cache block ids, full prompt tokens) for alias-mode hits:
        # the burst splices the blocks, and successful lanes pin the entries
        lane_prefix: dict[int, tuple[np.ndarray, np.ndarray]] = {}

        groups: dict[tuple, list[AdmissionItem]] = {}
        for it in items:
            bucket = pick_bucket(len(it.tokens) - it.cached_len, scfg)
            groups.setdefault(self._group_key(it, bucket), []).append(it)

        # Per admitted sequence: (lane, kv_len, next_token) + per-bucket KV.
        all_lanes: list[int] = []
        all_kv_len: list[int] = []
        all_next: list[jnp.ndarray] = []
        kv_chunks: list[tuple[jnp.ndarray, jnp.ndarray]] = []
        lane_cached: dict[int, int] = {}

        for (bucket, n_prefix, cached_len), group in sorted(groups.items()):
            k = len(group)
            width = max(W, k)
            toks = np.zeros((width, bucket), np.int32)
            lengths = np.zeros((width,), np.int32)
            for i, it in enumerate(group):
                suf = it.tokens[cached_len:]      # only the UNCACHED suffix
                toks[i, : len(suf)] = suf         # runs through prefill
                lengths[i] = len(suf)
            lengths[k:] = 1                       # dummy rows: benign gather idx
            batch = {"tokens": jnp.asarray(toks),
                     "lengths": jnp.asarray(lengths)}
            prefix_kv = None
            if cached_len:
                # Prefill skip (DESIGN.md §11): re-probe at admit time
                # (touch=True — recency + hit/miss bookkeeping), gather the
                # cached pages' K/V as the attention prefix, and prefill
                # the suffix only.  No cache mutation happens between the
                # final plan and here, so the probe must agree with it.
                # The gather feeds the suffix prefill's attention CONTEXT in
                # both hit-admission modes; only the page INSTALL differs
                # (copy duplicates the prefix into fresh lane pages, alias
                # splices the cache pages themselves — DESIGN.md §12).
                assert self.cache is not None
                n_pages = cached_len // self.kvcfg.page_size
                src = np.zeros((width, n_pages), np.int32)
                for i, it in enumerate(group):
                    cl, blks = self.cache.probe(it.tokens, touch=True)
                    assert cl == cached_len, \
                        f"cache changed between plan and admit: {cl} != {cached_len}"
                    src[i] = blks
                    if alias:
                        lane_prefix[int(it.lane)] = (
                            src[i].copy(), np.asarray(it.tokens, np.int32))
                # [width, P, L, ps, kv, hd] -> [width, L, P*ps, kv, hd]
                def _flat(pages):
                    g = pages[jnp.asarray(src)]
                    g = jnp.swapaxes(g, 1, 2)
                    return g.reshape(g.shape[0], g.shape[1], cached_len,
                                     *g.shape[4:])
                prefix_kv = (_flat(self.state.paged.k_pages),
                             _flat(self.state.paged.v_pages))
                batch["prefix_k"], batch["prefix_v"] = prefix_kv
            elif self.cache is not None and n_prefix == 0 \
                    and self.cfg.family not in ("ssm", "hybrid", "audio"):
                for it in group:
                    # no cached prefix: record the miss (and the trace
                    # event the sim replay consumes) at admit time
                    self.cache.probe(it.tokens, touch=True)
            if cfg.family == "audio":
                fr = np.stack([np.asarray(it.frames, np.float32)
                               for it in group])
                if k < width:
                    fr = np.concatenate(
                        [fr, np.zeros((width - k,) + fr.shape[1:], fr.dtype)])
                batch["frames"] = jnp.asarray(fr, self.dtype)
            if n_prefix:
                pe = np.stack([np.asarray(it.patches, np.float32)
                               for it in group])
                if k < width:
                    pe = np.concatenate(
                        [pe, np.zeros((width - k,) + pe.shape[1:], pe.dtype)])
                batch["patches"] = jnp.asarray(pe, self.dtype)

            with obs.span("repro.admission.prefill", owner=self.stats,
                          bucket=bucket, rows=k):
                res = self._prefill_fn(
                    (bucket, n_prefix, width, cached_len))(self.params, batch)

            rows = np.arange(k)
            lanes = np.asarray([it.lane for it in group], np.int32)
            if cfg.family in ("ssm", "hybrid"):
                # recurrent families seed decode with the last prompt token
                nxt = jnp.asarray([int(it.tokens[-1]) for it in group],
                                  jnp.int32)
                self._install_states(res.states, rows, lanes)
            else:
                nxt = jnp.argmax(res.last_logits[rows], axis=-1).astype(jnp.int32)
            if res.enc_out is not None:
                self.state = self.state._replace(
                    enc_out=self.state.enc_out.at[lanes].set(res.enc_out[rows]))
            all_next.append(nxt)
            all_lanes.extend(int(l) for l in lanes)
            # alias mode installs only the SUFFIX: the burst's lengths count
            # tokens whose KV the scatter writes, and the cached prefix
            # rides separately as prefix_lens (admit_prefill_many sums them
            # into seq_lens)
            inst_cached = 0 if alias else cached_len
            all_kv_len.extend(inst_cached + int(lengths[i]) + n_prefix
                              for i in rows)
            for it in group:
                lane_cached[int(it.lane)] = cached_len
            if res.kv is not None:
                ks, vs = res.kv                  # [width, L_kv, T_kv, kv, hd]
                if prefix_kv is not None and not alias:
                    # copy-based install: the lane gets its OWN pages for
                    # the full sequence, so prepend the cached prefix KV
                    # before the admission burst writes pages
                    pk, pv = prefix_kv
                    ks = jnp.concatenate([pk.astype(ks.dtype), ks], axis=2)
                    vs = jnp.concatenate([pv.astype(vs.dtype), vs], axis=2)
                    self.stats.cache_hit_copy_bytes += (
                        2 * k * int(np.prod(pk.shape[1:]))
                        * jnp.dtype(ks.dtype).itemsize)
                kv_chunks.append((ks[rows], vs[rows]))

        order = np.argsort(np.asarray(all_lanes, np.int32))
        lanes_np = np.asarray(all_lanes, np.int32)[order]
        with obs.span("repro.admission.kv_install", owner=self.stats):
            lanes_arr = jnp.asarray(lanes_np)
            next_tokens = jnp.concatenate(all_next)[jnp.asarray(order)]
            stats = None
            if kv_chunks:
                # Pad every bucket's KV to the widest time extent, then ONE
                # burst.
                t_max = max(c[0].shape[2] for c in kv_chunks)
                ks = jnp.concatenate(
                    [jnp.pad(c[0], ((0, 0), (0, 0), (0, t_max - c[0].shape[2]),
                                    (0, 0), (0, 0))) for c in kv_chunks])
                vs = jnp.concatenate(
                    [jnp.pad(c[1], ((0, 0), (0, 0), (0, t_max - c[1].shape[2]),
                                    (0, 0), (0, 0))) for c in kv_chunks])
                perm = jnp.asarray(order)
                kv_lens = jnp.asarray(np.asarray(all_kv_len, np.int32)[order])
                pb = pl = None
                if lane_prefix:
                    # burst-order [B, P] cache pages + [B] aliased token
                    # counts; rows with no hit carry zeros (inert: the splice
                    # and the refcount bump both mask on prefix length)
                    P = max(len(b) for b, _ in lane_prefix.values())
                    pb_np = np.zeros((lanes_np.shape[0], P), np.int32)
                    pl_np = np.zeros((lanes_np.shape[0],), np.int32)
                    for r, lane in enumerate(lanes_np):
                        rec = lane_prefix.get(int(lane))
                        if rec is not None:
                            pb_np[r, : len(rec[0])] = rec[0]
                            pl_np[r] = len(rec[0]) * self.kvcfg.page_size
                    pb, pl = jnp.asarray(pb_np), jnp.asarray(pl_np)
                paged, stats = pkv.admit_prefill_many(
                    self.kvcfg, self.state.paged, lanes_arr,
                    ks[perm], vs[perm], kv_lens, backend=self.alloc_backend,
                    policy=self.alloc_policy, tenants=self.tenants,
                    prefix_blocks=pb, prefix_lens=pl)
                self.stats.hmq_admit_bursts += 1
            else:
                # attention-free (rwkv6): no pages to allocate; activate lanes
                paged = self.state.paged
                kv_lens = jnp.asarray(np.asarray(all_kv_len, np.int32)[order])
                paged = paged._replace(
                    seq_lens=paged.seq_lens.at[lanes_arr].set(kv_lens),
                    active=paged.active.at[lanes_arr].set(True))
            self.state = self.state._replace(
                paged=paged,
                tokens=self.state.tokens.at[lanes_arr].set(next_tokens))
        if stats is not None:
            with obs.span("repro.admission.telemetry", owner=self.stats):
                self.stats.alloc_failures += int(self._fetch(stats.failed))
                self._note_burst(stats.per_tenant, stats.queue_live,
                                 stats.queue_capacity)
        with obs.span("repro.admission.readback", owner=self.stats):
            ok = self._fetch(paged.active)[lanes_np]
            if self.cfg.family not in ("ssm", "hybrid"):
                toks = self._fetch(next_tokens)
        failed = [int(l) for l, o in zip(lanes_np, ok) if not o]
        if self.cfg.family in ("ssm", "hybrid"):
            self.admitted_tokens = {}          # seed == last prompt token
        else:
            self.admitted_tokens = {
                int(l): int(t) for l, t, o in zip(lanes_np, toks, ok) if o}
        if lane_prefix:
            # pin the spliced entries for every lane that actually admitted
            # (the device refcount bump was gated on the same success mask)
            for lane, o in zip(lanes_np, ok):
                rec = lane_prefix.get(int(lane))
                if rec is None or not o:
                    continue
                blks, toks = rec
                self.cache.alias(toks, len(blks))
                self._aliased[int(lane)] = (
                    toks[: len(blks) * self.kvcfg.page_size], blks)
                self.stats.aliased_pages += len(blks)
        self.stats.admitted += len(items) - len(failed)
        with obs.span("repro.admission.telemetry", owner=self.stats):
            if kv_chunks:
                # contiguity telemetry over the lanes this batch installed:
                # how well the policy served admission's run-grants
                # (DESIGN.md §15)
                ok_lanes = [int(l) for l, o in zip(lanes_np, ok) if o]
                if ok_lanes:
                    ext, pgs = pkv.extent_stats(
                        self._fetch(paged.block_tables), ok_lanes)
                    self.stats.contiguous_extents += ext
                    self.stats.extent_pages += pgs
            self.stats.prefill_tokens_saved += sum(
                lane_cached.get(int(l), 0) for l, o in zip(lanes_np, ok) if o)
            self._sync_cache_stats()
        if failed:
            # reclaim orphaned partial grants (e.g. KV pages granted while
            # the state-slot packet failed) so failure never leaks the pool
            self.release(failed, completed=False)
        if any(it.cached_len for it in items):
            # hit-admission latency, comparable across copy/alias modes
            # (the fetch of ``active`` above already synced the device)
            self.stats.cache_hit_admits += 1
            self.stats.cache_hit_admit_us += \
                (time.perf_counter() - t_admit0) * 1e6
        return failed

    def _install_states(self, states: dec.RecurrentState, rows: np.ndarray,
                        lanes: np.ndarray) -> None:
        """Scatter per-layer recurrent prefill states into the lane slots."""
        rec = self.state.rec
        rows_j = jnp.asarray(rows)
        lanes_j = jnp.asarray(lanes)
        if self.cfg.family == "ssm":
            rec = dec.RecurrentState(
                ssm=rec.ssm.at[:, lanes_j].set(states.ssm[:, rows_j]),
                tm_prev=rec.tm_prev.at[:, lanes_j].set(
                    states.tm_prev[:, rows_j].astype(rec.tm_prev.dtype)),
                cm_prev=rec.cm_prev.at[:, lanes_j].set(
                    states.cm_prev[:, rows_j].astype(rec.cm_prev.dtype)))
        else:  # hybrid
            rec = dec.RecurrentState(
                ssm=rec.ssm.at[:, lanes_j].set(states.ssm[:, rows_j]),
                conv=rec.conv.at[:, lanes_j].set(
                    states.conv[:, rows_j].astype(rec.conv.dtype)))
        self.state = self.state._replace(rec=rec)

    def admit(self, lane: int, tokens: np.ndarray,
              frames: Optional[np.ndarray] = None,
              patches: Optional[np.ndarray] = None) -> bool:
        """Prefill one sequence and install it in `lane` (batch-of-one).

        Returns True when the sequence was admitted, False when the
        allocator rejected it (the lane is left inactive and clean).
        """
        return not self.admit_many([AdmissionItem(
            lane, np.asarray(tokens, np.int32), frames, patches)])

    # ---------------- decode ----------------

    def decode_step(self):
        """Run the jitted decode step on ``self.state``, then write its K/V
        into the pool in place.  Returns ``(logits, DecodeStats)``; in
        ``defer_refill`` mode the step's deferrable allocator ops go to
        ``pending_ops``."""
        # the live pool, taken before the step: the step hands its state
        # back without one, and a stand-in ``_decode`` may hand back an
        # older state whose pool is no longer live
        k, v = self.state.paged.k_pages, self.state.paged.v_pages
        new, logits, stats, *pending = self._decode(
            self.params, self.state, self._class_ids)
        self.pending_ops.extend(pending)
        if new.kv_write is not None:
            written = write_kv_in_place(k, v, new.kv_write)
            # donation marks the input deleted at dispatch: no device sync
            self.stats.kv_writes_in_place += int(k.is_deleted())
            k, v = written
        self.state = new._replace(
            paged=new.paged._replace(k_pages=k, v_pages=v), kv_write=None)
        return logits, stats

    def step(self) -> np.ndarray:
        """One decode step for all active lanes; returns next tokens.

        In ``defer_refill`` mode the step's deferrable allocator ops are
        appended to ``pending_ops`` for the multi-engine burst window to
        drain (one merged commit per window — DESIGN.md §10).

        Spans: ``repro.decode`` (``step`` = this step's number) with
        ``.dispatch`` (the jitted step and its K/V write), ``.readback``
        (every blocking read) and ``.telemetry`` (the host-side folds)."""
        st = self.stats
        with obs.span("repro.decode", owner=st, step=st.decode_steps):
            with obs.span("repro.decode.dispatch", owner=st):
                _, stats = self.decode_step()
            with obs.span("repro.decode.readback", owner=st):
                failed = int(self._fetch(stats.failed))
                bursts = int(self._fetch(stats.bursts))
                hits = int(self._fetch(stats.stash_hits))
                misses = int(self._fetch(stats.stash_misses))
                burst = self._fetch((stats.tenant, stats.queue_live,
                                     stats.queue_capacity))
                hist = self._fetch(stats.stash_depth_hist)
                tokens = self._fetch(self.state.tokens)
            with obs.span("repro.decode.telemetry", owner=st):
                # mirror the executable's compile accounting: with a shared
                # multi-engine CountingJit every shard reports the SAME
                # counter (1 executable for all of them), not a per-shard
                # contribution
                st.decode_compiles = self._decode.compiles
                st.decode_compile_us = self._decode.compile_us
                st.decode_steps += 1
                st.alloc_failures += failed
                st.decode_bursts += bursts
                st.stash_hits += hits
                st.stash_misses += misses
                self._fold_burst(*burst, issued=bool(bursts))
                if not st.stash_depth_hist:
                    st.stash_depth_hist = [0] * hist.shape[0]
                st.stash_depth_hist = [
                    a + int(b) for a, b in zip(st.stash_depth_hist, hist)]
        return tokens

    # ---------------- completion ----------------

    def release(self, lanes: Sequence[int], completed: bool = True,
                kv_tokens: Optional[dict] = None) -> None:
        """Free everything the lanes own via FREE_ALL request packets.

        ``completed=False`` reclaims lanes whose admission failed (any
        partially granted blocks return to the pool) without counting them
        as served.

        ``kv_tokens`` (prefix cache on only) maps lanes to the token
        sequence whose KV they hold (``Scheduler.kv_token_prefix``): those
        lanes' full pages are demoted into the cache FIRST — kept pages
        retagged to ``CACHE_OWNER`` so this commit's FREE_ALLs skip them,
        eviction victims riding the same burst as single frees.

        Lanes that spliced shared cache pages at admission (alias mode) get
        the same treatment regardless of ``completed``: their pins drop and
        the shared block ids ride this commit as single OP_FREEs, because
        their FREE_ALLs match on lane ownership and skip CACHE_OWNER pages.
        """
        st = self.stats
        with obs.span("repro.release", owner=st, lanes=list(lanes)):
            extra = None
            if completed and self.cache is not None and kv_tokens:
                # demote BEFORE unalias: the pins keep this insert's budget
                # evictions away from prefix pages other live lanes still
                # read
                with obs.span("repro.release.demote", owner=st):
                    extra = self._demote_lanes(
                        {l: kv_tokens[l] for l in lanes if l in kv_tokens})
            with obs.span("repro.release.burst", owner=st):
                if self._aliased:
                    shared = self._unalias_lanes(lanes)
                    if shared:
                        extra = (extra or []) + shared
                pkts = release_packet_array(list(lanes), self.kvcfg.max_lanes)
                paged, stats = pkv.release_packets(
                    self.kvcfg, self.state.paged, jnp.asarray(pkts),
                    backend=self.alloc_backend, policy=self.alloc_policy,
                    tenants=self.tenants, extra_free=extra)
                st.hmq_release_bursts += 1
            with obs.span("repro.release.telemetry", owner=st):
                self._note_burst(stats.per_tenant, stats.queue_live,
                                 stats.queue_capacity)
                self.state = self.state._replace(paged=paged)
                self._sync_cache_stats()
            if completed:
                st.completed += len(lanes)

    def preempt(self, lanes: Sequence[int]) -> None:
        """Evict running lanes: FREE_ALL every block they own (pages, state
        slot, scratch, stashed pages) so the pool is immediately available
        for a higher-priority admission.  The scheduler re-queues the
        corresponding requests with their generated prefix (DESIGN.md §10);
        nothing is counted as completed."""
        self.release(lanes, completed=False)
        self.stats.preemptions += len(lanes)

    @property
    def live_pages(self) -> int:
        return int(self._fetch(pkv.live_pages(self.state.paged, self.tenants)))

    @property
    def free_pages(self) -> int:
        """Allocatable KV pages right now (admission-policy input)."""
        return int(self._fetch(
            self.state.paged.alloc.free_top[self.tenants.kv.size_class]))
