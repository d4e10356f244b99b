"""Paged KV cache managed by the SpeedMalloc support-core.

This is the production integration of the paper's technique (DESIGN.md §2):
KV pages are the "user data"; the block tables / free lists are the
segregated metadata owned exclusively by the support-core step.  The serving
engine issues fixed-format request packets each decode step — exactly the
paper's main-core → support-core signal protocol, realized as dataflow.

Storage layout
--------------
One *page* holds ``page_size`` tokens of K and V for **all** KV layers
(a single allocation per page covers every layer — one HMQ request per
sequence per ``page_size`` tokens, keeping allocator traffic tiny relative
to compute):

    k_pages, v_pages : [num_pages, num_kv_layers, page_size, kv_heads, head_dim]
    block_tables     : [max_lanes, max_pages_per_lane] int32 (metadata)
    seq_lens         : [max_lanes] int32                      (metadata)

Size classes: class 0 = KV pages; class 1 (optional) = recurrent-state slots
for SSM/hybrid archs (zamba2, rwkv6) — constant-size per-lane state managed
through the same centralized allocator.

Beyond-paper feature: **sliding-window page recycling** — for SWA archs
(mixtral, gemma3 local layers) pages that fall fully behind the attention
window are recycled, bounding pages/lane to ``window/page_size + 1``.

Two-tier front-end (DESIGN.md §7): when ``stash_size > 0`` each lane keeps a
small LIFO stash of pre-granted pages (``core/lane_stash.py``).  Decode pops
boundary pages from the stash and pushes recycled dead pages back to it, so
steady-state steps never touch the central allocator; one bulk HMQ burst
(gated behind an any-live-packet ``lax.cond``) periodically refills every
below-watermark lane and flushes overflow.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

if TYPE_CHECKING:  # runtime import is lazy: repro.alloc <-> repro.core would
    # otherwise cycle through the repro.core package __init__
    from ..alloc.service import (AllocService, BurstStats, TenantHandle,
                                 TenantStats)
from .freelist import FreeListState
from .lane_stash import (LaneStashState, below_watermark, init_stash,
                         stash_clear, stash_pop, stash_push, stash_push_batch,
                         stash_set_rows, validate_stash_params)
from .packets import NO_BLOCK, NO_LANE
from .support_core import StepStats  # noqa: F401  (re-export)

KV_CLASS = 0
STATE_CLASS = 1

#: Synthetic owner id for KV pages demoted into the prefix cache
#: (DESIGN.md §11).  Far above any lane id, below the FREE_ALL lane-list
#: pad sentinel (2**31 - 1) and the int32 ceiling.  A lane's FREE_ALL
#: matches ``owner == lane`` and therefore skips demoted pages, while a
#: single OP_FREE is owner-agnostic (``owner >= 0``), so eviction reclaims
#: them through the ordinary free path.
CACHE_OWNER = 1 << 30

#: Tenant names the paged-KV allocator registers on its AllocService.  The
#: registration ORDER fixes the size-class indices: kv_pages is always class
#: 0 (KV_CLASS) and state_slots — when configured — class 1 (STATE_CLASS),
#: preserving the historical constants; the scratch tenant takes the next
#: free class.
KV_TENANT = "kv_pages"
STATE_TENANT = "state_slots"
SCRATCH_TENANT = "scratch"


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    num_kv_layers: int
    kv_heads: int
    head_dim: int
    page_size: int
    num_pages: int
    max_lanes: int
    max_pages_per_lane: int
    dtype: jnp.dtype = jnp.bfloat16
    # SSM/hybrid lane-state slots (0 disables the extra size class)
    state_slots: int = 0
    state_dim: int = 0
    # Per-lane prefill/decode workspace slots — the third tenant sharing the
    # one support-core (0 disables it).  Each admitted lane mallocs one
    # workspace block in the admission burst and frees it in its release
    # burst, so scratch traffic rides the same HMQ batches as KV pages and
    # state slots (the paper's many-clients-one-core claim, exercised).
    scratch_slots: int = 0
    # Per-lane page-stash front-end (DESIGN.md §7).  stash_size == 0 disables
    # the tier (decode then issues its HMQ burst exactly as before, still
    # gated behind the any-live-packet predicate).  When enabled, a lane
    # whose stash depth drops below ``stash_watermark`` gets ``stash_refill``
    # pages in the next bulk refill burst.
    stash_size: int = 0
    stash_watermark: int = 2
    stash_refill: int = 4

    def __post_init__(self):
        if self.stash_size:
            validate_stash_params(self.stash_size, self.stash_watermark,
                                  self.stash_refill)

    @property
    def tokens_capacity(self) -> int:
        return self.num_pages * self.page_size


class PagedKVState(NamedTuple):
    alloc: FreeListState          # segregated metadata (support-core owned)
    block_tables: jnp.ndarray     # [max_lanes, max_pages_per_lane] int32
    seq_lens: jnp.ndarray         # [max_lanes] int32
    active: jnp.ndarray           # [max_lanes] bool
    k_pages: jnp.ndarray          # [num_pages, L, page_size, kv_heads, head_dim]
    v_pages: jnp.ndarray          # same
    state_slot: jnp.ndarray       # [max_lanes] int32 (NO_BLOCK if none)
    lane_state: jnp.ndarray       # [state_slots, state_dim] recurrent state storage
    stash: LaneStashState         # per-lane page-stash front-end (DESIGN.md §7)
    scratch_slot: jnp.ndarray     # [max_lanes] int32 workspace block (NO_BLOCK if none)


class DecodeStats(NamedTuple):
    """Decode-step telemetry: the support-core stats plus the stash tier.

    ``bursts`` is 0/1 — whether this step actually issued a support-core HMQ
    batch (steady-state stash-served steps skip it entirely).  ``failed``
    counts only *on-path* failures (a boundary lane that got no page);
    failed speculative refills are benign and tracked separately in
    ``refill_failed`` (``core.failed`` still holds the raw total).
    ``stash_depth_hist[d]`` counts ACTIVE lanes whose end-of-step stash
    depth is d (shape ``[stash_size + 1]``) — a per-lane depth histogram
    that localizes refill storms under mixed-length traffic: a healthy
    steady state masses near the top bins, a storm piles lanes at 0..1.
    ``tenant`` is the per-tenant (per size class) breakdown of the burst;
    ``queue_live`` / ``queue_capacity`` its slot occupancy (DESIGN.md §9).
    """

    core: StepStats
    tenant: TenantStats          # [C]-shaped per-tenant burst breakdown
    failed: jnp.ndarray          # on-path (emergency) malloc failures
    refill_failed: jnp.ndarray   # benign speculative-refill failures
    stash_hits: jnp.ndarray      # boundary pages served by the stash
    stash_misses: jnp.ndarray    # boundary pages that needed a central malloc
    bursts: jnp.ndarray          # 0/1 support-core steps issued
    stash_depth_hist: jnp.ndarray  # [stash_size + 1] int32 active-lane histogram
    queue_live: jnp.ndarray      # non-NOP slots in this step's burst queue
    queue_capacity: jnp.ndarray  # static burst queue capacity (traced const)

    # forwarders so DecodeStats reads like the StepStats it extends
    @property
    def mallocs(self):
        return self.core.mallocs

    @property
    def frees(self):
        return self.core.frees

    @property
    def blocks_allocated(self):
        return self.core.blocks_allocated

    @property
    def blocks_freed(self):
        return self.core.blocks_freed


class PagedTenants(NamedTuple):
    """One engine's view of its allocator clients on an AllocService.

    Everything the paged-KV layer needs to speak to the support-core:
    the service plus the KV-page / state-slot / scratch tenant handles.
    With the default per-config service the handles sit at the historical
    class constants (``kv.size_class == KV_CLASS`` ...); on a SHARED
    multi-engine service each shard's handles carry its own namespaced
    classes (``"e1/kv_pages"`` etc. — DESIGN.md §10), and every function in
    this module indexes metadata through the handles, never the constants.
    """

    service: "AllocService"
    kv: "TenantHandle"
    state: Optional["TenantHandle"] = None
    scratch: Optional["TenantHandle"] = None

    @property
    def handles(self) -> tuple:
        """The registered handles, in class order (for telemetry loops)."""
        return tuple(t for t in (self.kv, self.state, self.scratch)
                     if t is not None)

    def class_id_array(self) -> jnp.ndarray:
        """``[len(handles)]`` int32 — the namespaced size-class ids, in
        handle order (kv, then state/scratch when configured).  The value a
        shard passes into a tenant-agnostic decode step each call, so N
        shards share ONE executable (DESIGN.md §13)."""
        return jnp.asarray([t.size_class for t in self.handles], jnp.int32)

    def with_class_ids(self, class_ids) -> "PagedTenants":
        """This view with every handle's ``size_class`` replaced by the
        matching element of ``class_ids`` (``[len(handles)]`` int32, handle
        order — :meth:`class_id_array`'s layout).  Called inside a jitted
        step with a traced operand, it yields handles whose class ids are
        traced scalars: the burst builder then emits them as queue DATA
        instead of baking one shard's constants into the executable.  The
        service reference (host-side config: tenant table, policy, backend)
        stays static — only the per-shard indices are traced."""
        class_ids = jnp.asarray(class_ids, jnp.int32)
        fields: dict = {"service": self.service}
        idx = 0
        for name in ("kv", "state", "scratch"):
            t = getattr(self, name)
            if t is not None:
                fields[name] = t._replace(size_class=class_ids[idx])
                idx += 1
            else:
                fields[name] = None
        return PagedTenants(**fields)


def _tenant_spec(cfg: PagedKVConfig) -> list[tuple[str, int]]:
    spec = [(KV_TENANT, cfg.num_pages)]
    if cfg.state_slots:
        spec.append((STATE_TENANT, cfg.state_slots))
    if cfg.scratch_slots:
        spec.append((SCRATCH_TENANT, cfg.scratch_slots))
    return spec


def register_paged_tenants(svc: "AllocService", cfg: PagedKVConfig,
                           namespace: str = "") -> PagedTenants:
    """Register this config's tenant set on ``svc`` (optionally namespaced)
    and return the engine-side view.  The multi-engine entry point: each
    shard calls this ONCE on the one shared service before ``init_state``."""
    handles = svc.register_tenants(_tenant_spec(cfg), namespace=namespace)
    by_base = {t.base_name: t for t in handles}
    return PagedTenants(service=svc, kv=by_base[KV_TENANT],
                        state=by_base.get(STATE_TENANT),
                        scratch=by_base.get(SCRATCH_TENANT))


@functools.lru_cache(maxsize=None)
def paged_service(cfg: PagedKVConfig) -> "AllocService":
    """The AllocService every paged-KV allocator touch goes through.

    One service per config (cached — the service is static host-side
    configuration, safe to share across jitted traces).  Tenants register in
    the order that pins the historical class constants: ``kv_pages`` ->
    KV_CLASS, ``state_slots`` -> STATE_CLASS, then ``scratch``.  Policy and
    backend stay per-commit arguments, threaded from the engine exactly like
    the old ``backend=`` plumbing.
    """
    from ..alloc.service import AllocService
    svc = AllocService()
    svc.register_tenants(_tenant_spec(cfg))
    return svc


@functools.lru_cache(maxsize=None)
def paged_tenants(cfg: PagedKVConfig) -> PagedTenants:
    """The default (un-namespaced, per-config service) tenant view."""
    svc = paged_service(cfg)
    return PagedTenants(
        service=svc,
        kv=svc.tenant(KV_TENANT),
        state=svc.tenant(STATE_TENANT) if cfg.state_slots else None,
        scratch=svc.tenant(SCRATCH_TENANT) if cfg.scratch_slots else None,
    )


def num_alloc_classes(cfg: PagedKVConfig) -> int:
    """Size classes (== tenants) this config's allocator carries."""
    return paged_service(cfg).num_classes


def init_paged_kv(cfg: PagedKVConfig,
                  policy: Optional[str] = None,
                  alloc: Optional[FreeListState] = None,
                  tenants: Optional[PagedTenants] = None) -> PagedKVState:
    """Fresh paged-KV state.  ``policy`` must name the allocator policy the
    engine will run (a policy may have a custom ``init``); ``None`` resolves
    the ``REPRO_ALLOC_POLICY`` env knob, like every burst.

    ``alloc`` installs an EXISTING allocator state instead of creating one —
    the multi-engine path, where one shared ``FreeListState`` (covering
    every shard's namespaced classes) is created once by the shared service
    and threaded through all shards.  ``tenants`` names the service to
    create the metadata on when ``alloc`` is not given.
    """
    shape = (cfg.num_pages, cfg.num_kv_layers, cfg.page_size, cfg.kv_heads, cfg.head_dim)
    if alloc is None:
        svc = (tenants or paged_tenants(cfg)).service
        alloc = svc.init_state(policy=policy)
    return PagedKVState(
        alloc=alloc,
        block_tables=jnp.full((cfg.max_lanes, cfg.max_pages_per_lane), NO_BLOCK, jnp.int32),
        seq_lens=jnp.zeros((cfg.max_lanes,), jnp.int32),
        active=jnp.zeros((cfg.max_lanes,), bool),
        k_pages=jnp.zeros(shape, cfg.dtype),
        v_pages=jnp.zeros(shape, cfg.dtype),
        state_slot=jnp.full((cfg.max_lanes,), NO_BLOCK, jnp.int32),
        lane_state=jnp.zeros((max(cfg.state_slots, 1), max(cfg.state_dim, 1)), jnp.float32),
        stash=init_stash(cfg.max_lanes, cfg.stash_size),
        scratch_slot=jnp.full((cfg.max_lanes,), NO_BLOCK, jnp.int32),
    )


# --------------------------------------------------------------------------
# Admission (prefill): B lanes, T tokens each -> ceil(len_i / page_size)
# pages per lane, allocated by ONE support-core HMQ burst for the whole
# batch (the paper's batched server-client admission).
# --------------------------------------------------------------------------

def admit_prefill_many(
    cfg: PagedKVConfig,
    state: PagedKVState,
    lanes: jnp.ndarray,           # [B] int32, distinct lane ids
    k: jnp.ndarray,               # [B, L, T, kv_heads, head_dim]
    v: jnp.ndarray,
    lengths: jnp.ndarray,         # [B] int32, each <= T
    backend: Optional[str] = None,
    policy: Optional[str] = None,
    tenants: Optional[PagedTenants] = None,
    prefix_blocks: Optional[jnp.ndarray] = None,  # [B, P] int32 cache pages
    prefix_lens: Optional[jnp.ndarray] = None,    # [B] int32 aliased tokens
) -> tuple[PagedKVState, BurstStats]:
    """Admit B prefilled sequences with a single support-core step.

    The burst carries one KV-page malloc per lane — plus one
    recurrent-state-slot malloc and one scratch-workspace malloc when the
    config carries those tenants — staged through the service's
    :class:`~repro.alloc.BurstBuilder`, so the whole admission batch costs
    exactly one HMQ burst and every packet group resolves through its own
    ticket.  With ``lanes`` in ascending order the block assignment is
    bit-identical to B sequential :func:`admit_prefill` calls: the HMQ
    arbiter serves round-0 mallocs in lane order, from the same free pool.

    Lanes must be distinct (one request packet per lane).

    Zero-copy prefix aliasing (DESIGN.md §12): when ``prefix_blocks`` /
    ``prefix_lens`` are given, ``k`` / ``v`` / ``lengths`` describe ONLY
    the suffix tokens.  Each lane's block-table row is spliced as
    ``[prefix_blocks[b, :prefix_lens[b] // page_size], fresh suffix pages]``
    — the cache-owned prefix pages are read in place (their refcounts bump
    by one per new reference; no K/V bytes move), only suffix pages are
    malloc'd and scattered, and ``seq_lens`` covers prefix + suffix.
    ``prefix_lens`` must be page-aligned (the cache only holds full pages)
    and ``prefix_blocks`` padded with :data:`~repro.core.packets.NO_BLOCK`.
    Shared pages are read-only by construction: decode appends always land
    at page index >= the prefix length, in the lane's private tail.
    """
    B, L, T = k.shape[:3]
    ps = cfg.page_size
    max_pages = (T + ps - 1) // ps
    lanes = lanes.astype(jnp.int32)
    n_pages = (lengths.astype(jnp.int32) + ps - 1) // ps                # [B]
    if prefix_blocks is not None:
        prefix_blocks = jnp.asarray(prefix_blocks, jnp.int32)
        if prefix_blocks.shape[1] == 0:          # no lane aliases anything
            prefix_blocks = None
    if prefix_blocks is None:
        n_prefix = jnp.zeros((B,), jnp.int32)
        prefix_lens = jnp.zeros((B,), jnp.int32)
    else:
        prefix_lens = jnp.asarray(prefix_lens, jnp.int32)
        n_prefix = prefix_lens // ps                                    # [B]
    # A sequence whose pages would overflow its block-table row can never be
    # addressed: force ALL of its packets to fail (overwide arg) instead of
    # leaking unreferenced pages or a stranded state/scratch slot.  The
    # admission then reports it in `failed`.
    fits = n_prefix + n_pages <= cfg.max_pages_per_lane
    # forced-fail must exceed the response width R (overwide -> fail), which
    # the stash pre-charge packets may widen beyond max_pages.
    pre = cfg.stash_refill if cfg.stash_size else 0
    resp_width = max(max_pages, pre)
    forced_fail = jnp.int32(resp_width + 1)

    tenants = tenants if tenants is not None else paged_tenants(cfg)
    svc = tenants.service
    burst = svc.new_burst()
    # The KV pages are requested with the CONTIGUITY hint: under a
    # run-aware policy (buddy, DESIGN.md §15) each lane's predicted page
    # count lands as one aligned extent when the free map has one, so the
    # block-table row reads as few long runs instead of scattered singles;
    # under freelist/bitmap the hint lowers to a plain OP_MALLOC at staging
    # time.  Grant/fail semantics are identical either way — a shortfall
    # falls back to singles, never to a failure the other policies would
    # not also report — so tokens stay bit-identical across policies.
    t_kv = burst.malloc_run(tenants.kv, lanes,
                            n=jnp.where(fits, n_pages, forced_fail))
    t_state = burst.malloc(tenants.state, lanes,
                           n=jnp.where(fits, jnp.int32(1), forced_fail)) \
        if cfg.state_slots else None
    t_scratch = burst.malloc(tenants.scratch, lanes,
                             n=jnp.where(fits, jnp.int32(1), forced_fail)) \
        if cfg.scratch_slots else None
    if cfg.stash_size:
        # Stash pre-charge: one extra malloc packet per lane fills the
        # admitted lane's stash with a refill batch, so early decode steps
        # are served by the front tier instead of bursting immediately.
        # The packet rides the SAME burst at refill priority (OP_REFILL:
        # after every plain malloc), so under scarcity the pre-charge fails
        # first and admission itself is unaffected (an empty stash is
        # benign).
        t_pre = burst.refill(tenants.kv, lanes,
                             n=jnp.where(fits, jnp.int32(pre), forced_fail))
    alloc, res = svc.commit(state.alloc, burst,
                            max_blocks_per_req=resp_width,
                            backend=backend, policy=policy)
    stats = res.stats
    if cfg.stash_size:
        # `failed` should mean "admission packets that failed": a failed
        # pre-charge is benign (the lane just starts with an empty stash)
        # and must not read as an allocation failure in engine telemetry.
        # The per-tenant kv_pages breakdown is corrected the same way, so
        # aggregate and per-tenant admission-failure counts always agree.
        kv_required = jnp.sum(~res.ok_for(t_kv)).astype(jnp.int32)
        required = kv_required
        for t in (t_state, t_scratch):
            if t is not None:
                required = required + jnp.sum(~res.ok_for(t)).astype(jnp.int32)
        pt = stats.per_tenant
        pt = pt._replace(
            failed=pt.failed.at[tenants.kv.size_class].set(kv_required))
        stats = stats._replace(core=stats.core._replace(failed=required),
                               per_tenant=pt)

    pages = res.blocks_for(t_kv)[:, :max_pages]              # [B, max_pages]
    # A lane is admitted only if EVERY packet it needs succeeded; under pool
    # scarcity one tenant can still succeed while another fails — those
    # orphaned grants stay owned by the (inactive) lane until FREE_ALL
    # releases it (ServingEngine.admit_many reclaims failed lanes itself).
    # The stash pre-charge packet is NOT required: admission stands even
    # when the pre-charge failed (the lane just starts with an empty stash).
    got = res.ok_for(t_kv)                                   # [B]
    for t in (t_state, t_scratch):
        if t is not None:
            got = got & res.ok_for(t)
    # Block table rows for the admitted lanes.
    p_lim = min(max_pages, cfg.max_pages_per_lane)
    if prefix_blocks is None:
        rows = jnp.full((B, cfg.max_pages_per_lane), NO_BLOCK, jnp.int32)
        rows = rows.at[:, :p_lim].set(
            jnp.where(got[:, None], pages[:, :p_lim], NO_BLOCK))
    else:
        # Splice: row = [shared prefix pages | fresh suffix pages | pad].
        M = cfg.max_pages_per_lane
        P = prefix_blocks.shape[1]
        pos = jnp.arange(M, dtype=jnp.int32)[None, :]                # [1, M]
        pref = jnp.take_along_axis(
            prefix_blocks,
            jnp.broadcast_to(jnp.clip(pos, 0, P - 1), (B, M)), axis=1)
        suf = jnp.take_along_axis(
            pages, jnp.broadcast_to(
                jnp.clip(pos - n_prefix[:, None], 0, max_pages - 1),
                (B, M)), axis=1)
        in_pref = pos < n_prefix[:, None]
        in_suf = (pos >= n_prefix[:, None]) \
            & (pos < (n_prefix + n_pages)[:, None])
        rows = jnp.where(got[:, None] & in_pref, pref,
                         jnp.where(got[:, None] & in_suf, suf, NO_BLOCK))
        # Aliased pages gain one reference per successfully admitted lane
        # (control-plane bump, no HMQ traffic; padded/failed slots map to
        # a positive OOB sentinel — negative ids would wrap even under
        # mode="drop").
        valid_pref = (jnp.arange(P, dtype=jnp.int32)[None, :]
                      < n_prefix[:, None]) & got[:, None]
        sentinel = jnp.int32(alloc.refcount.shape[1])
        alloc = svc.bump_refcounts(
            alloc, tenants.kv,
            jnp.where(valid_pref, prefix_blocks, sentinel).reshape(-1))
    block_tables = state.block_tables.at[lanes].set(rows)

    # Scatter KV into the allocated pages:
    # [B, L, T, kv, hd] -> [B * max_pages, L, ps, kv, hd]
    pad = max_pages * ps - T
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kp = kp.reshape(B, L, max_pages, ps, cfg.kv_heads, cfg.head_dim).swapaxes(1, 2)
    vp = vp.reshape(B, L, max_pages, ps, cfg.kv_heads, cfg.head_dim).swapaxes(1, 2)
    valid = (jnp.arange(max_pages, dtype=jnp.int32)[None, :] < n_pages[:, None]) \
        & got[:, None]
    dst = jnp.where(valid, pages, cfg.num_pages)             # OOB sentinel -> dropped
    flat = (B * max_pages, L, ps, cfg.kv_heads, cfg.head_dim)
    k_pages = state.k_pages.at[dst.reshape(-1)].set(
        kp.reshape(flat).astype(cfg.dtype), mode="drop")
    v_pages = state.v_pages.at[dst.reshape(-1)].set(
        vp.reshape(flat).astype(cfg.dtype), mode="drop")

    slots = jnp.where(got, res.blocks_for(t_state)[:, 0], NO_BLOCK) \
        if t_state is not None else jnp.full((B,), NO_BLOCK, jnp.int32)
    scratch = jnp.where(got, res.blocks_for(t_scratch)[:, 0], NO_BLOCK) \
        if t_scratch is not None else jnp.full((B,), NO_BLOCK, jnp.int32)
    stash = state.stash
    if cfg.stash_size:
        # Install the pre-charge grants.  Recorded whenever the pre-charge
        # packet itself succeeded (even for a lane whose admission failed:
        # the pages are owner-mapped to the lane either way, and the
        # engine's failure path releases the lane with FREE_ALL — clearing
        # the stash row keeps the I5 partition exact).
        pc_got = res.ok_for(t_pre)
        stash = stash_set_rows(stash, lanes, res.blocks_for(t_pre)[:, :pre],
                               pre, pc_got)
    new = state._replace(
        alloc=alloc,
        block_tables=block_tables,
        seq_lens=state.seq_lens.at[lanes].set(
            jnp.where(got, prefix_lens + lengths.astype(jnp.int32), 0)),
        active=state.active.at[lanes].set(got),
        k_pages=k_pages,
        v_pages=v_pages,
        state_slot=state.state_slot.at[lanes].set(slots),
        stash=stash,
        scratch_slot=state.scratch_slot.at[lanes].set(scratch),
    )
    return new, stats


def admit_prefill(
    cfg: PagedKVConfig,
    state: PagedKVState,
    lane: jnp.ndarray,            # scalar int32
    k: jnp.ndarray,               # [L, T, kv_heads, head_dim]
    v: jnp.ndarray,
    length: jnp.ndarray,          # scalar int32, <= T
    backend: Optional[str] = None,
    policy: Optional[str] = None,
    tenants: Optional[PagedTenants] = None,
) -> tuple[PagedKVState, BurstStats]:
    """Admit one prefilled sequence (batch-of-one :func:`admit_prefill_many`)."""
    lanes = jnp.asarray(lane, jnp.int32).reshape(1)
    lengths = jnp.asarray(length, jnp.int32).reshape(1)
    return admit_prefill_many(cfg, state, lanes, k[None], v[None], lengths,
                              backend=backend, policy=policy, tenants=tenants)


# --------------------------------------------------------------------------
# Decode: append one token per active lane; allocate pages at boundaries.
# --------------------------------------------------------------------------

class PendingDecodeOps(NamedTuple):
    """Deferrable central-allocator traffic one decode step produced.

    Emitted by :func:`decode_append` in ``defer_refill`` mode instead of
    committing refills/flushes in-step: the multi-engine burst window
    accumulates these across a scheduling quantum (for EVERY engine shard)
    and serves them all with ONE merged support-core commit (DESIGN.md
    §10).  None of it is on the token critical path — only emergency
    mallocs are, and those stay in-step.
    """

    below: jnp.ndarray         # [L] bool — lanes wanting a stash refill
    flush_mask: jnp.ndarray    # [L] bool — recycled pages that overflowed
    flush_blocks: jnp.ndarray  # [L] int32 — their block ids (NO_BLOCK else)


class KVWrite(NamedTuple):
    """Where one decode step's new K/V goes: row ``lane`` of ``k``/``v``
    lands at ``k_pages[dst_page[lane], :, offset[lane]]``.  ``dst_page`` is
    ``num_pages`` (out of range, dropped) for lanes that write nothing."""

    dst_page: jnp.ndarray      # [lanes] int32
    offset: jnp.ndarray        # [lanes] int32
    k: jnp.ndarray             # [lanes, L, kv_heads, head_dim] pool dtype
    v: jnp.ndarray


def write_kv(k_pages: jnp.ndarray, v_pages: jnp.ndarray, w: KVWrite):
    """Scatter one decode step's K/V rows into the pool.  Jitted with both
    pools donated, it writes them in place (DESIGN.md §16)."""
    with jax.named_scope("kv_write"):
        return (k_pages.at[w.dst_page, :, w.offset].set(w.k, mode="drop"),
                v_pages.at[w.dst_page, :, w.offset].set(w.v, mode="drop"))


def decode_append(cfg: PagedKVConfig, state: PagedKVState,
                  new_k: jnp.ndarray, new_v: jnp.ndarray, **kw):
    """:func:`decode_alloc` followed by its :func:`write_kv`: one appended
    token per active lane, pool included.  Returns what ``decode_alloc``
    does, less the ``KVWrite``."""
    state, stats, w, *pending = decode_alloc(cfg, state, new_k, new_v, **kw)
    k_pages, v_pages = write_kv(state.k_pages, state.v_pages, w)
    return (state._replace(k_pages=k_pages, v_pages=v_pages), stats,
            *pending)


def decode_alloc(
    cfg: PagedKVConfig,
    state: PagedKVState,
    new_k: jnp.ndarray,           # [max_lanes, L, kv_heads, head_dim]
    new_v: jnp.ndarray,
    window: Optional[int] = None,  # SWA window (tokens); enables page recycling
    backend: Optional[str] = None,
    policy: Optional[str] = None,
    tenants: Optional[PagedTenants] = None,
    defer_refill: bool = False,
):
    """Advance every active lane by one token through the two-tier
    allocator, and say where its K/V goes; the pool itself is left alone.

    Tier 1 (stash, when ``cfg.stash_size > 0``): page-boundary lanes pop
    their new page from the per-lane stash with pure vector ops, and
    SWA-recycled dead pages push back to the stash first.  Tier 2 (central
    support-core): ONE bulk HMQ burst — staged as typed ``BurstBuilder``
    ops with per-lane ``where`` masks — carries (a) emergency 1-page
    mallocs for lanes whose stash pop missed, (b) ``stash_refill``-page
    refills for every below-watermark lane, and (c) ``free`` flushes for
    recycled pages that found the stash full; ``commit(gated=True)`` skips
    the whole step when no packet is live, so steady-state stash-served
    steps never touch the central allocator.  With the stash disabled the
    burst is exactly the pre-stash one (bit-identical behaviour), still
    gated by the same all-NOP predicate.

    ``defer_refill=True`` (static; the multi-engine async decode loop) keeps
    ONLY the on-path emergency mallocs in the in-step burst and returns the
    refill/flush traffic as a third :class:`PendingDecodeOps` result, to be
    merged across engines and steps into one commit per burst window.
    Deferral never changes token output: refills only move pages between the
    central stack and lane stashes, and flushed dead pages stay owner-mapped
    (hence reclaimable by ``FREE_ALL``) until the window commit frees them.

    Returns ``(state, DecodeStats, KVWrite)`` — plus ``PendingDecodeOps``
    when ``defer_refill`` is set.  ``state`` holds the input's pool arrays
    unchanged; :func:`write_kv` applies the ``KVWrite`` to them.
    """
    with jax.named_scope("alloc"):
        ps = cfg.page_size
        L = cfg.max_lanes
        S = cfg.stash_size
        pos = state.seq_lens                                     # [lanes]
        lane_ids = jnp.arange(L, dtype=jnp.int32)
        needs_page = state.active & (pos % ps == 0) \
            & (pos // ps < cfg.max_pages_per_lane)   # table range guard

        # --- tier 1: pop the boundary page from the stash (no allocator step)
        stash = state.stash
        if S:
            stash, popped, got_stash = stash_pop(stash, needs_page)
            missed = needs_page & ~got_stash
        else:
            popped = jnp.full((L,), NO_BLOCK, jnp.int32)
            got_stash = jnp.zeros((L,), bool)
            missed = needs_page

        # --- SWA page recycling: dead pages push to the stash first; only
        # overflow (stash full / stash off) goes back through the central tier.
        if window is not None:
            # After appending at `pos`, tokens < pos+1-window are dead.  A page p
            # (covering [p*ps, (p+1)*ps)) is dead when (p+1)*ps <= pos+1-window.
            dead_page_idx = (pos + 1 - window) // ps - 1         # highest fully-dead page
            has_dead = state.active & (dead_page_idx >= 0) & ((dead_page_idx + 1) * ps <= pos + 1 - window)
            # Free exactly the newest dead page each step (at most one page can
            # newly die per appended token), read from the block table.
            safe_idx = jnp.clip(dead_page_idx, 0, cfg.max_pages_per_lane - 1)
            dead_block = state.block_tables[lane_ids, safe_idx]
            already = dead_block == NO_BLOCK                     # freed in a previous step
            recycle = has_dead & ~already
            if S:
                stash, pushed = stash_push(stash, dead_block, recycle)
                overflow = recycle & ~pushed                     # stash full: flush
            else:
                overflow = recycle
            # the dead page leaves the table whether it was stashed or flushed
            block_tables = state.block_tables.at[
                jnp.where(recycle, lane_ids, L), safe_idx
            ].set(NO_BLOCK, mode="drop")
        else:
            overflow = None
            block_tables = state.block_tables

        # --- tier 2: one bulk HMQ burst (emergency + refill + flush), gated.
        # In defer mode the burst carries ONLY the on-path emergency mallocs;
        # refills and flushes accumulate in the caller's burst window.
        tenants = tenants if tenants is not None else paged_tenants(cfg)
        svc, kv = tenants.service, tenants.kv
        burst = svc.new_burst()
        t_malloc = burst.malloc(kv, lane_ids, 1, where=missed)
        below = below_watermark(stash, state.active, cfg.stash_watermark) \
            if S else jnp.zeros((L,), bool)
        if S and not defer_refill:
            # refill priority: scheduled after every plain malloc in the batch,
            # so a bulk refill can never starve another lane's boundary
            # allocation.
            t_refill = burst.refill(kv, lane_ids, cfg.stash_refill, where=below)
        if overflow is not None and not defer_refill:
            burst.free(kv, lane_ids, dead_block, where=overflow)
        alloc, res = svc.commit(
            state.alloc, burst,
            max_blocks_per_req=max(1, cfg.stash_refill if S and not defer_refill
                                   else 1),
            backend=backend, policy=policy, gated=True)

        # --- install newly obtained pages into block tables (stash pop wins;
        # emergency grants cover the misses)
        new_blocks = res.blocks_for(t_malloc)[:, 0]              # [lanes]
        e_got = res.ok_for(t_malloc) & missed
        got = got_stash | e_got
        page_for_lane = jnp.where(got_stash, popped, new_blocks)
        tbl_idx = jnp.clip(pos // ps, 0, cfg.max_pages_per_lane - 1)
        block_tables = block_tables.at[
            jnp.where(got, lane_ids, L), tbl_idx
        ].set(jnp.where(got, page_for_lane, NO_BLOCK), mode="drop")

        # --- install bulk-refill grants into the stash
        if S and not defer_refill:
            r_got = res.ok_for(t_refill) & below
            stash = stash_push_batch(stash,
                                     res.blocks_for(t_refill)[:, :cfg.stash_refill],
                                     cfg.stash_refill, r_got)
            refill_failed = jnp.sum(below & ~r_got).astype(jnp.int32)
        else:
            # deferred refills fail (benignly) at the window commit, not here
            refill_failed = jnp.zeros((), jnp.int32)

    with jax.named_scope("kv_write"):
        # --- each lane's new token goes to its current page
        writable = state.active & (got | ~needs_page)
        cur_block = block_tables[lane_ids, tbl_idx]              # [lanes]
        dst_page = jnp.where(writable & (cur_block != NO_BLOCK), cur_block, cfg.num_pages)
        write = KVWrite(dst_page=dst_page, offset=pos % ps,
                        k=new_k.astype(cfg.dtype), v=new_v.astype(cfg.dtype))

    with jax.named_scope("alloc"):
        new = state._replace(
            alloc=alloc,
            block_tables=block_tables,
            seq_lens=jnp.where(writable, pos + 1, pos),
            stash=stash,
        )
        dstats = DecodeStats(
            core=res.stats.core,
            tenant=res.stats.per_tenant,
            failed=jnp.sum(missed & ~e_got).astype(jnp.int32),
            refill_failed=refill_failed,
            stash_hits=jnp.sum(got_stash).astype(jnp.int32),
            stash_misses=jnp.sum(missed).astype(jnp.int32),
            bursts=res.live,
            stash_depth_hist=stash_depth_histogram(cfg, stash, state.active),
            queue_live=res.stats.queue_live,
            queue_capacity=res.stats.queue_capacity,
        )
    if not defer_refill:
        return new, dstats, write
    if overflow is not None:
        pending = PendingDecodeOps(
            below=below, flush_mask=overflow,
            flush_blocks=jnp.where(overflow, dead_block, NO_BLOCK))
    else:
        pending = PendingDecodeOps(
            below=below, flush_mask=jnp.zeros((L,), bool),
            flush_blocks=jnp.full((L,), NO_BLOCK, jnp.int32))
    return new, dstats, write, pending


def stash_depth_histogram(cfg: PagedKVConfig, stash: LaneStashState,
                          active: jnp.ndarray) -> jnp.ndarray:
    """``[stash_size + 1]`` int32 histogram of active lanes' stash depth.

    Bin d counts active lanes sitting at depth d; inactive lanes are
    dropped (positive OOB sentinel).  With the stash disabled this is one
    bin holding the active-lane count.
    """
    bins = cfg.stash_size + 1
    depth = jnp.clip(stash.depth, 0, cfg.stash_size)
    return jnp.zeros((bins,), jnp.int32).at[
        jnp.where(active, depth, bins)].add(1, mode="drop")


def empty_decode_stats(cfg: PagedKVConfig,
                       tenants: Optional[PagedTenants] = None) -> DecodeStats:
    """All-zero DecodeStats matching this config's histogram and tenant
    shapes (the attention-free decode branch and other no-allocator steps).
    ``tenants`` supplies the class count when the engine rides a shared
    multi-engine service (whose ``[C]`` spans every shard)."""
    z = jnp.zeros((), jnp.int32)
    from ..alloc.service import empty_burst_stats
    C = tenants.service.num_classes if tenants is not None \
        else num_alloc_classes(cfg)
    zero = empty_burst_stats(C)
    return DecodeStats(core=zero.core, tenant=zero.per_tenant,
                       failed=z, refill_failed=z,
                       stash_hits=z, stash_misses=z, bursts=z,
                       stash_depth_hist=jnp.zeros((cfg.stash_size + 1,),
                                                  jnp.int32),
                       queue_live=z, queue_capacity=z)


# --------------------------------------------------------------------------
# Prefix cache: KV pages that survive request completion (DESIGN.md §11).
# Host-side metadata only — page payloads never move; ownership is retagged
# to CACHE_OWNER on demotion and pages are reclaimed via ordinary OP_FREEs
# on eviction.
# --------------------------------------------------------------------------

def default_page_hash(prev: int, page_tokens: np.ndarray) -> int:
    """Rolling per-page hash: fold one page of token ids into the running
    prefix hash.  Page i's key depends on every token in pages 0..i, so a
    probe can stop at the first divergent page.  Injectable (tests force
    collisions to prove the exact-token verification below catches them)."""
    h = prev & 0xFFFFFFFFFFFFFFFF
    for t in page_tokens:
        h = (h * 1000003 ^ (int(t) + 0x9E3779B9)) & 0xFFFFFFFFFFFFFFFF
    return h


@dataclasses.dataclass
class CacheEntry:
    """One cached KV page: the page's block id plus the FULL token prefix
    it closes (pages 0..i of some completed sequence).  ``pkey`` is the
    prefix's byte image — the content-stable identity used for exact
    verification, dedupe, and eviction-policy bookkeeping (block ids get
    recycled by the allocator; content keys never lie)."""
    key: int                 # rolling hash of the prefix (bucket index)
    tokens: np.ndarray       # [(i+1) * page_size] int32 full prefix
    pkey: bytes              # tokens.tobytes() — exact content identity
    block: int               # KV page id, owner-mapped to CACHE_OWNER


class PrefixCache:
    """Token-prefix → KV-page cache with pluggable eviction.

    Keyed per page by rolling prefix hash, so any prefix length can hit;
    every lookup verifies the full token prefix against the entry (hash
    collisions can never alias wrong-content pages).  The cache holds at
    most ``budget_pages`` pages; those pages stay allocated in the KV
    tenant's class (owner ``CACHE_OWNER``), so the budget is charged
    against the tenant quota and admission page math stays exact.

    Victim selection delegates to an :class:`repro.alloc.eviction
    .EvictionPolicy` keyed by entry content.  Evicting an entry cascades to
    its descendants (longer prefixes that extend it): probes walk from page
    0, so an entry whose ancestor is gone would be unreachable garbage.

    ``trace`` records the logical (insert/probe) event stream — replayable
    through :func:`repro.sim.policies.replay_prefix_trace` for differential
    testing of eviction policies against the live engine.
    """

    def __init__(self, page_size: int, budget_pages: int, policy=None,
                 hash_fn: Optional[Callable[[int, np.ndarray], int]] = None):
        from ..alloc.eviction import get_eviction
        self.page_size = int(page_size)
        self.budget = int(budget_pages)
        self.policy = policy if policy is not None else get_eviction(None)
        self.hash_fn = hash_fn or default_page_hash
        self._chains: dict[int, list[CacheEntry]] = {}
        self._by_pkey: dict[bytes, CacheEntry] = {}
        # pkey -> outstanding lane references (zero-copy aliases, DESIGN.md
        # §12).  A pinned entry (refs > 0) sits in a live block table and
        # must never be evicted — its page would be rewritten under a
        # running lane.
        self._aliases: dict[bytes, int] = {}
        self.hits = 0            # probed requests that reused >= 1 page
        self.misses = 0          # probed requests with no reusable prefix
        self.inserts = 0         # pages demoted into the cache
        self.evictions = 0       # pages evicted (policy picks + cascades)
        self.dup_skips = 0       # demoted pages already cached (left to FREE_ALL)
        self.aliases = 0         # pages spliced into lane tables zero-copy
        self.trace: list[tuple] = []

    @property
    def pages(self) -> int:
        """Pages currently held (== entries; one page per entry)."""
        return len(self._by_pkey)

    def blocks(self) -> np.ndarray:
        """Sorted block ids held by the cache (the I5 cache partition)."""
        return np.sort(np.asarray(
            [e.block for e in self._by_pkey.values()], np.int64))

    # -- probe ------------------------------------------------------------
    def probe(self, tokens, touch: bool = False) -> tuple[int, list[int]]:
        """Longest cached prefix of ``tokens``: ``(cached_len, blocks)``.

        ``cached_len`` is a multiple of ``page_size`` and strictly less
        than ``len(tokens)`` — at least one suffix token always prefills,
        so admission still produces the seed logits.  ``touch=True`` is the
        admission-time lookup: it bumps eviction-policy recency, the
        hit/miss counters, and the replay trace; plan-time probes peek
        without side effects (they may run several times per admission).
        """
        tokens = np.asarray(tokens, np.int32)
        ps = self.page_size
        n = len(tokens) // ps
        if n and n * ps == len(tokens):
            n -= 1
        h = 0
        blocks: list[int] = []
        for i in range(n):
            h = self.hash_fn(h, tokens[i * ps:(i + 1) * ps])
            entry = None
            want = tokens[:(i + 1) * ps]
            for e in self._chains.get(h, ()):
                if len(e.tokens) == len(want) and \
                        np.array_equal(e.tokens, want):
                    entry = e
                    break
            if entry is None:
                break
            blocks.append(entry.block)
            if touch:
                self.policy.on_hit(entry.pkey)
        if touch:
            self.trace.append(("probe", tuple(int(t) for t in tokens)))
            if blocks:
                self.hits += 1
            else:
                self.misses += 1
        return len(blocks) * ps, blocks

    # -- alias (zero-copy hit admission) ----------------------------------
    def alias(self, tokens, n_pages: int) -> None:
        """Pin the first ``n_pages`` entries of ``tokens``' cached chain: a
        lane spliced their pages into its block table (DESIGN.md §12).  The
        caller bumps the device refcounts; this records the host-side pin so
        eviction skips the entries while any lane reads them.  One call per
        admitted lane; balanced by :meth:`unalias` at lane release."""
        tokens = np.asarray(tokens, np.int32)
        ps = self.page_size
        n = int(n_pages)
        for i in range(n):
            pkey = tokens[:(i + 1) * ps].tobytes()
            self._aliases[pkey] = self._aliases.get(pkey, 0) + 1
        self.aliases += n
        self.trace.append(
            ("alias", tuple(int(t) for t in tokens[:n * ps]), n))

    def unalias(self, tokens, n_pages: int) -> None:
        """Drop one lane's pin on the first ``n_pages`` entries of
        ``tokens``' chain (the lane released or was preempted; its single
        OP_FREEs decrement the device refcounts on the same burst)."""
        tokens = np.asarray(tokens, np.int32)
        ps = self.page_size
        n = int(n_pages)
        for i in range(n):
            pkey = tokens[:(i + 1) * ps].tobytes()
            left = self._aliases.get(pkey, 0) - 1
            if left > 0:
                self._aliases[pkey] = left
            else:
                self._aliases.pop(pkey, None)
        self.trace.append(
            ("unalias", tuple(int(t) for t in tokens[:n * ps]), n))

    @property
    def pinned(self) -> int:
        """Entries currently pinned by at least one lane alias."""
        return len(self._aliases)

    # -- demote (insert) --------------------------------------------------
    def insert(self, tokens, blocks) -> tuple[list[int], list[int], list[int]]:
        """Demote a completed sequence's full pages into the cache.

        ``blocks[i]`` is the page covering tokens ``[i*ps, (i+1)*ps)``.
        Returns ``(kept, skipped, evicted)`` block lists: ``kept`` must be
        owner-retagged to :data:`CACHE_OWNER` by the caller, ``skipped``
        (already-cached duplicates and over-budget tails) stay lane-owned
        for the lane's FREE_ALL to sweep, ``evicted`` are cache-owned
        victims the caller must free with single OP_FREEs.
        """
        tokens = np.asarray(tokens, np.int32)
        ps = self.page_size
        n = min(len(tokens) // ps, len(blocks))
        keep: list[tuple[int, np.ndarray, bytes, int]] = []
        skipped: list[int] = []
        h = 0
        for i in range(n):
            h = self.hash_fn(h, tokens[i * ps:(i + 1) * ps])
            prefix = tokens[:(i + 1) * ps]
            pkey = prefix.tobytes()
            if pkey in self._by_pkey:
                skipped.append(int(blocks[i]))
                self.dup_skips += 1
                self.policy.on_hit(pkey)
            else:
                keep.append((h, prefix, pkey, int(blocks[i])))
        self.trace.append(("insert", tuple(int(t) for t in tokens), n))

        evicted: list[int] = []
        while keep and self.pages + len(keep) > self.budget and self.pages:
            batch = self._evict_one()
            if not batch:        # every resident entry is pinned
                break
            evicted.extend(batch)
        if keep and self.pages + len(keep) > self.budget:
            # budget smaller than the insertable room (pinned residents, or
            # a chain longer than the whole budget): keep only the
            # shallowest pages (prefix property needs contiguity from page
            # 0 of the chain)
            cut = max(0, self.budget - self.pages)
            skipped.extend(b for _, _, _, b in keep[cut:])
            keep = keep[:cut]
        if keep:
            # an eviction cascade may have removed this chain's cached
            # ancestor mid-insert, orphaning the whole chain — unreachable
            # entries would leak pages, so skip the insert instead
            first = keep[0][1]
            if len(first) > ps and \
                    first[:-ps].tobytes() not in self._by_pkey:
                skipped.extend(b for _, _, _, b in keep)
                keep = []
        for h, prefix, pkey, block in keep:
            entry = CacheEntry(key=h, tokens=prefix, pkey=pkey, block=block)
            self._chains.setdefault(h, []).append(entry)
            self._by_pkey[pkey] = entry
            self.policy.on_insert(pkey)
            self.inserts += 1
        kept = [b for _, _, _, b in keep]
        return kept, skipped, evicted

    # -- evict ------------------------------------------------------------
    def _drop(self, entry: CacheEntry) -> None:
        chain = self._chains.get(entry.key, [])
        if entry in chain:
            chain.remove(entry)
            if not chain:
                del self._chains[entry.key]
        del self._by_pkey[entry.pkey]

    def _evict_one(self) -> list[int]:
        """Evict the policy's next evictABLE victim plus its descendants;
        returns the freed block ids (empty when the cache is drained or
        every remaining entry is pinned).

        Pinned entries (aliased into a live lane's block table, DESIGN.md
        §12) are skipped — and so is any victim with a pinned descendant,
        because the cascade would orphan it.  Skipped victims re-enter the
        policy via ``on_insert`` in skip order, a deterministic requeue the
        trace replay reproduces exactly."""
        skipped: list[bytes] = []
        freed: list[int] = []
        for _ in range(len(self._by_pkey)):
            pkey = self.policy.victim()
            if pkey is None:
                break
            victim = self._by_pkey[pkey]
            doomed = [victim] + [
                e for e in self._by_pkey.values()
                if len(e.pkey) > len(pkey) and e.pkey.startswith(pkey)]
            if any(e.pkey in self._aliases for e in doomed):
                skipped.append(pkey)
                continue
            for e in doomed:
                self._drop(e)
                if e is not victim:
                    self.policy.on_remove(e.pkey)
            self.evictions += len(doomed)
            freed = [e.block for e in doomed]
            break
        for pk in skipped:
            self.policy.on_insert(pk)
        return freed

    def evict_pages(self, n: int) -> list[int]:
        """Evict victims until at least ``n`` pages are freed, the cache
        drains, or only pinned (aliased) entries remain.  The admission
        shortfall path: freed blocks must be OP_FREEd by the caller before
        the pages are allocatable."""
        self.trace.append(("evict", int(n)))
        freed: list[int] = []
        while len(freed) < n and self.pages:
            batch = self._evict_one()
            if not batch:        # every resident entry is pinned
                break
            freed.extend(batch)
        return freed


# --------------------------------------------------------------------------
# Completion: free everything a set of lanes owns, via OP_FREE/FREE_ALL
# request packets — the scheduler's lane-lifecycle release path.
# --------------------------------------------------------------------------

def release_packets(
    cfg: PagedKVConfig,
    state: PagedKVState,
    lane_ids: jnp.ndarray,        # [K] int32 packet slots; NO_LANE = empty slot
    backend: Optional[str] = None,
    policy: Optional[str] = None,
    tenants: Optional[PagedTenants] = None,
    extra_free=None,
) -> tuple[PagedKVState, BurstStats]:
    """Release lanes through FREE_ALL request packets in one support-core step.

    ``lane_ids`` is a compact packet array (the scheduler emits one slot per
    completed lane, padded with :data:`~repro.core.packets.NO_LANE`).  Every
    block the named lanes own — KV pages and, when configured, the
    recurrent-state slot and the scratch workspace — is freed by the
    support-core's deferred-free path (one ``free_all`` ticket per tenant,
    one burst total); host metadata rows (block table, seq_lens, active,
    state_slot, scratch_slot) are then cleared.  Lanes may appear in any
    order; duplicate ids are harmless (FREE_ALL is idempotent within a
    step).

    ``extra_free`` rides additional single-block KV frees on the same burst
    — the prefix cache's eviction victims (owner ``CACHE_OWNER``, which the
    FREE_ALLs deliberately skip; single frees are owner-agnostic).  Pages
    the caller just demoted were owner-retagged BEFORE this commit, so the
    lane's FREE_ALL leaves them resident.
    """
    lane_ids = lane_ids.astype(jnp.int32)
    valid = lane_ids >= 0
    safe = jnp.clip(lane_ids, 0, cfg.max_lanes - 1)
    tenants = tenants if tenants is not None else paged_tenants(cfg)
    svc = tenants.service
    burst = svc.new_burst()
    stage_release_ops(tenants, burst, safe, valid)
    if extra_free is not None and len(extra_free):
        blocks = jnp.asarray(extra_free, jnp.int32)
        burst.free(tenants.kv, jnp.zeros((blocks.shape[0],), jnp.int32),
                   blocks)
    alloc, res = svc.commit(state.alloc, burst, max_blocks_per_req=1,
                            backend=backend, policy=policy)
    release_mask = jnp.zeros((cfg.max_lanes,), bool).at[
        jnp.where(valid, safe, cfg.max_lanes)].set(True, mode="drop")
    return clear_released_lanes(state._replace(alloc=alloc),
                                release_mask), res.stats


def stage_release_ops(tenants: PagedTenants, burst,
                      lane_ids: jnp.ndarray, valid) -> None:
    """Stage one FREE_ALL packet per configured tenant per lane slot onto an
    open burst (shared by :func:`release_packets` and the multi-engine
    window commit, which merges many shards' releases into one burst)."""
    for t in tenants.handles:
        burst.free_all(t, lane_ids, where=valid)


def clear_released_lanes(state: PagedKVState,
                         release_mask: jnp.ndarray) -> PagedKVState:
    """Clear the host-side metadata rows of released lanes (block table,
    seq_lens, active, state/scratch slots, stash rows).  The blocks
    themselves return to the central stack via the FREE_ALL packets — which
    the caller either committed already (:func:`release_packets`) or staged
    into a pending burst window (the multi-engine async loop, where the
    lane's pages stay owner-mapped until the window commit sweeps them)."""
    keep = ~release_mask
    return state._replace(
        block_tables=jnp.where(release_mask[:, None], NO_BLOCK, state.block_tables),
        seq_lens=jnp.where(keep, state.seq_lens, 0),
        active=state.active & keep,
        state_slot=jnp.where(keep, state.state_slot, NO_BLOCK),
        # stashed pages are owner-mapped to the lane, so the FREE_ALL
        # reclaims them centrally; the host only clears the rows
        stash=stash_clear(state.stash, release_mask),
        scratch_slot=jnp.where(keep, state.scratch_slot, NO_BLOCK),
    )


def release_lanes(
    cfg: PagedKVConfig,
    state: PagedKVState,
    release_mask: jnp.ndarray,    # [max_lanes] bool
    backend: Optional[str] = None,
    policy: Optional[str] = None,
    tenants: Optional[PagedTenants] = None,
) -> tuple[PagedKVState, BurstStats]:
    """Dense-mask release (legacy shape; routed through the packet path)."""
    lane_ids = jnp.where(release_mask,
                         jnp.arange(cfg.max_lanes, dtype=jnp.int32), NO_LANE)
    return release_packets(cfg, state, lane_ids, backend=backend,
                           policy=policy, tenants=tenants)


# --------------------------------------------------------------------------
# Reference gather (testing + XLA serve path): materialize per-layer KV.
# --------------------------------------------------------------------------

def gather_kv(
    cfg: PagedKVConfig,
    state: PagedKVState,
    layer: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Return (k, v, valid_mask) for one layer.

    k, v: [max_lanes, max_pages_per_lane * page_size, kv_heads, head_dim]
    valid: [max_lanes, max_pages_per_lane * page_size] bool
    """
    tbl = state.block_tables                                  # [lanes, P]
    safe = jnp.where(tbl == NO_BLOCK, 0, tbl)
    k = state.k_pages[safe, layer]                            # [lanes, P, ps, kv, hd]
    v = state.v_pages[safe, layer]
    lanes, P = tbl.shape
    ps = cfg.page_size
    k = k.reshape(lanes, P * ps, cfg.kv_heads, cfg.head_dim)
    v = v.reshape(lanes, P * ps, cfg.kv_heads, cfg.head_dim)
    tok = jnp.arange(P * ps, dtype=jnp.int32)[None, :]
    valid = (tok < state.seq_lens[:, None]) & (tbl != NO_BLOCK).repeat(ps, axis=1)
    valid = valid & state.active[:, None]
    return k, v, valid


def gather_kv_window(
    cfg: PagedKVConfig,
    state: PagedKVState,
    layer: int,
    window: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Windowed gather: only the page slots that can still be live under a
    sliding window of `window` tokens (exploits support-core page recycling —
    dead slots were freed and would gather garbage anyway).

    Returns (k, v, pos, valid):
      k, v  [lanes, W_slots * page_size, kv_heads, head_dim]
      pos   [lanes, W_slots * page_size] absolute token positions
      valid [lanes, W_slots * page_size]
    """
    ps = cfg.page_size
    w_slots = min(-(-window // ps) + 1, cfg.max_pages_per_lane)
    lanes = cfg.max_lanes
    # first potentially-live slot per lane (clamped so the slice stays in range)
    first = jnp.clip((state.seq_lens - window) // ps, 0,
                     cfg.max_pages_per_lane - w_slots)
    slot = first[:, None] + jnp.arange(w_slots, dtype=jnp.int32)[None, :]
    tbl = jnp.take_along_axis(state.block_tables, slot, axis=1)  # [lanes, W]
    safe = jnp.where(tbl == NO_BLOCK, 0, tbl)
    k = state.k_pages[safe, layer]                    # [lanes, W, ps, kv, hd]
    v = state.v_pages[safe, layer]
    k = k.reshape(lanes, w_slots * ps, cfg.kv_heads, cfg.head_dim)
    v = v.reshape(lanes, w_slots * ps, cfg.kv_heads, cfg.head_dim)
    pos = (slot[:, :, None] * ps
           + jnp.arange(ps, dtype=jnp.int32)[None, None, :]).reshape(lanes, -1)
    valid = (pos < state.seq_lens[:, None]) \
        & (tbl != NO_BLOCK).repeat(ps, axis=1) & state.active[:, None]
    return k, v, pos, valid


def live_pages(state: PagedKVState,
               tenants: PagedTenants) -> jnp.ndarray:
    """Currently allocated KV pages (telemetry / blowup tracking).

    ``tenants`` is REQUIRED: it selects the engine's namespaced KV class on
    a (possibly shared multi-engine) allocator state.  The old default to
    the global ``KV_CLASS`` constant silently read engine-0's class on
    namespaced shards — callers now thread their own handle set (the
    single-engine default is ``paged_tenants(cfg)``)."""
    return state.alloc.used[tenants.kv.size_class]


def kv_pages_in_use(cfg: PagedKVConfig, state: PagedKVState):
    """Host-side [num_pages] bool: pages referenced by any block table."""
    import numpy as np
    tbl = np.asarray(state.block_tables)
    in_use = np.zeros((cfg.num_pages,), bool)
    in_use[tbl[tbl != NO_BLOCK]] = True
    return in_use


def extent_stats(block_tables, lanes=None) -> tuple[int, int]:
    """Host-side ``(contiguous_extents, pages)`` over block-table rows.

    An extent is a maximal run of CONSECUTIVE page ids inside one lane's
    table prefix (``NO_BLOCK`` entries end the row).  ``pages / extents``
    is the mean run length — 1.0 when every page is an island (the
    freelist/bitmap steady state under churn), larger when a run-aware
    policy (buddy, DESIGN.md §15) granted admission contiguous runs.
    ``lanes`` restricts the count to a subset of rows (e.g. just-admitted
    lanes).  Telemetry only; not jittable.
    """
    import numpy as np
    tbl = np.asarray(block_tables)
    if lanes is not None:
        tbl = tbl[np.asarray(lanes)]
    extents = pages = 0
    for row in tbl:
        held = row[row != NO_BLOCK]
        if held.size == 0:
            continue
        pages += int(held.size)
        extents += 1 + int(np.count_nonzero(np.diff(held) != 1))
    return extents, pages


def compact_kv(
    cfg: PagedKVConfig,
    state: PagedKVState,
    tenants: Optional[PagedTenants] = None,
    max_moves: Optional[int] = None,
) -> tuple[PagedKVState, int]:
    """Between-burst-window KV compaction pass (DESIGN.md §15).

    Repacks sole-owner lane pages (device ``refcount == 1`` and
    ``owner == lane`` — never aliased prefix pages, never
    :data:`CACHE_OWNER` cache residents, never stash pages, which live
    outside the block tables) toward one end of the page address space,
    sliding past immovable residents: the movable pages take the lowest
    (or highest) cells of the combined movable+free id set, so the torn
    holes between them coalesce into one extent.  Both directions are
    planned host-side and the pass keeps whichever scores better on
    (largest free run, fewest free extents) — buddy packs low so its
    survivors repack low, the freelist's LIFO stack pops high ids so its
    survivors repack high — and it is a no-op when neither plan beats
    the current state.

    Each move copies the page's K/V payload, rewrites the one block-table
    slot naming it, and migrates the page's allocator metadata (moves may
    CHAIN — a vacated cell can be another move's destination; the
    functional ``.at[dst].set(pages[src])`` gathers from the pre-pass
    arrays, so chains are safe).  ``free_top``/``used`` and every counter
    are unchanged, so I1–I6 hold verbatim afterwards
    (:func:`validate_paged_kv` is the test oracle).  The free stack is
    rebuilt in ascending id order, matching the buddy policy's
    address-ordered convention.

    Host-side planning + one device gather/scatter for the payload; call
    it BETWEEN burst windows (it reads and rebuilds allocator rows that a
    concurrent burst would race).  Returns ``(new_state, pages_moved)``;
    ``max_moves`` caps the migration for incremental passes (the kept
    moves are the ones nearest the packing end, which stay chain-safe
    under truncation).
    """
    tenants = tenants if tenants is not None else paged_tenants(cfg)
    cls = tenants.kv.size_class
    alloc = state.alloc
    owner = np.asarray(alloc.owner[cls])
    refc = np.asarray(alloc.refcount[cls])
    top = int(np.asarray(alloc.free_top)[cls])
    tbl = np.asarray(state.block_tables)
    free_ids = sorted(int(b) for b in np.asarray(alloc.free_stack[cls])[:top])
    if not free_ids:
        return state, 0

    movable: dict[int, tuple[int, int]] = {}       # id -> (lane, slot)
    for lane in range(tbl.shape[0]):
        for slot, b in enumerate(tbl[lane]):
            b = int(b)
            if b != NO_BLOCK and owner[b] == lane and refc[b] == 1:
                movable[b] = (lane, slot)
    if not movable:
        return state, 0

    def run_score(ids) -> tuple[int, int]:
        """(largest free run, -number of free extents): bigger is better."""
        best = run = extents = 0
        prev = None
        for f in sorted(ids):
            if prev is None or f != prev + 1:
                extents += 1
                run = 0
            run += 1
            best = max(best, run)
            prev = f
        return best, -extents

    movable_ids = sorted(movable)
    cells = sorted(set(movable_ids) | set(free_ids))
    M = len(movable_ids)
    cap = M if max_moves is None else min(max_moves, M)

    def plan(direction: str):
        targets = cells[:M] if direction == "low" else cells[-M:]
        pairs = [(s, d) for s, d in zip(movable_ids, targets) if s != d]
        if direction == "high":
            pairs.reverse()            # keep the moves nearest the top end
        pairs = pairs[:cap]
        after = (set(free_ids) | {s for s, _ in pairs}) \
            - {d for _, d in pairs}
        return pairs, run_score(after), after

    lo_pairs, lo_score, lo_after = plan("low")
    hi_pairs, hi_score, hi_after = plan("high")
    pairs, score, free_after = (lo_pairs, lo_score, lo_after) \
        if lo_score >= hi_score else (hi_pairs, hi_score, hi_after)
    if score <= run_score(free_ids) or not pairs:
        return state, 0

    src_np = np.asarray([s for s, _ in pairs], np.int32)
    dst_np = np.asarray([d for _, d in pairs], np.int32)
    src_ids = jnp.asarray(src_np)
    dst_ids = jnp.asarray(dst_np)

    # payload: KV-class block ids ARE page ids (registration order, §10);
    # the RHS gathers from the PRE-pass arrays, so chained moves are safe
    k_pages = state.k_pages.at[dst_ids].set(state.k_pages[src_ids])
    v_pages = state.v_pages.at[dst_ids].set(state.v_pages[src_ids])

    lanes_np = np.asarray([movable[s][0] for s, _ in pairs])
    slots_np = np.asarray([movable[s][1] for s, _ in pairs])
    tbl2 = tbl.copy()
    tbl2[lanes_np, slots_np] = dst_np

    # metadata: dst inherits the page's identity from the PRE-pass arrays;
    # only cells vacated and not refilled become free
    own2, ref2 = owner.copy(), refc.copy()
    own2[dst_np] = owner[src_np]
    ref2[dst_np] = refc[src_np]
    vacated = np.asarray(sorted(set(src_np.tolist())
                                - set(dst_np.tolist())), np.int32)
    own2[vacated] = -1
    ref2[vacated] = 0

    row = np.asarray(alloc.free_stack[cls]).copy()
    free_sorted = sorted(free_after)
    row[: len(free_sorted)] = np.asarray(free_sorted, np.int32)

    alloc = alloc._replace(
        free_stack=alloc.free_stack.at[cls].set(jnp.asarray(row)),
        owner=alloc.owner.at[cls].set(jnp.asarray(own2)),
        refcount=alloc.refcount.at[cls].set(jnp.asarray(ref2)),
    )
    state = state._replace(alloc=alloc, block_tables=jnp.asarray(tbl2),
                           k_pages=k_pages, v_pages=v_pages)
    return state, len(pairs)


def validate_paged_kv(cfg: PagedKVConfig, state: PagedKVState,
                      tenants: Optional[PagedTenants] = None,
                      cache: Optional[PrefixCache] = None) -> None:
    """Host-side invariant check for the full paged-KV allocator state:
    I1–I4 on the segregated metadata plus I5 — every KV page is exactly one
    of {central free stack, lane stash, block-table referenced, prefix
    cache} — and the exact I6 refcount identity: every KV page's device
    refcount equals its block-table in-degree across all lanes plus its
    cache and stash references (DESIGN.md §12).  Failures raise
    :class:`~repro.core.freelist.FreelistInvariantError` labelled with
    the tenant names, so a tenant-quota bug reads as a per-tenant report.

    ``tenants`` points the check at the engine's namespaced classes on a
    shared multi-engine state (I1–I4 then cover EVERY shard's classes; I5's
    stash partition and the I6 identity run against this engine's own KV
    class).  ``cache`` extends the partition with the engine's
    :class:`PrefixCache` pages (owner-mapped to :data:`CACHE_OWNER`);
    without it, any demoted page fails the partition sum — leaks are loud
    either way.
    """
    from .freelist import validate_freelist
    tenants = tenants if tenants is not None else paged_tenants(cfg)
    # Independent recomputation of every KV page's reference count: one per
    # block-table slot naming it (aliased pages count once per lane), one
    # for stash membership, one for cache residency.  The device refcount
    # plane must match element for element.
    expected = np.zeros((state.alloc.max_capacity,), np.int64)
    tbl = np.asarray(state.block_tables)
    np.add.at(expected, tbl[tbl != NO_BLOCK], 1)
    sp = np.asarray(state.stash.pages)
    sd = np.asarray(state.stash.depth)
    for lane in range(sp.shape[0]):
        np.add.at(expected, sp[lane, :int(sd[lane])], 1)
    if cache is not None:
        np.add.at(expected, cache.blocks(), 1)
    validate_freelist(
        state.alloc,
        stash_pages=state.stash.pages,
        stash_depth=state.stash.depth,
        in_use=kv_pages_in_use(cfg, state),
        stash_class=tenants.kv.size_class,
        tenant_names=tenants.service.tenant_names(),
        cache_pages=cache.blocks() if cache is not None else None,
        cache_owner=CACHE_OWNER if cache is not None else None,
        refcount_expected=expected,
    )
