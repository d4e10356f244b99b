"""`repro.alloc` — the first-class client API of the support-core.

Every client of the SpeedMalloc support-core talks through this module
(DESIGN.md §9).  The paper's claim is that ONE general-purpose lightweight
core serves *many* main cores and can *adopt new allocator designs*; the
reproduction makes both claims exercisable:

* :class:`AllocService` — a service object owning the tenant table, the
  allocator policy, and backend dispatch.  Clients never hand-roll
  ``RequestQueue`` layouts or un-permute response indices again.
* :class:`BurstBuilder` — typed op staging: ``malloc`` / ``refill`` /
  ``free`` / ``free_all`` calls append fixed-format packet slots and return
  :class:`Ticket`\\ s; after :meth:`AllocService.commit` runs the burst as
  ONE support-core step, each ticket resolves to its own rows of the
  response queue (``blocks_for`` / ``ok_for``) — the builder owns the
  offset bookkeeping that used to be copy-pasted at every call site.
* **Named tenants** — ``register_tenant("kv_pages", capacity=...)`` maps a
  client onto a size class with a hard per-tenant block quota (its class
  capacity: segregated metadata gives hard isolation, one tenant can never
  consume another's pool), per-tenant occupancy, and a per-tenant
  :class:`TenantStats` breakdown on every burst.
* :class:`~repro.alloc.policies.AllocatorPolicy` — the pluggable central
  design (free-list vs bitmap first-fit; ``REPRO_ALLOC_POLICY``).

The service object is static host-side configuration: construct it (and
register tenants) OUTSIDE jit, then call :meth:`commit` freely inside jitted
steps — it closes over nothing traced, and all shapes it produces are static.

Migration from the loose PR-0..3 functions (full table in DESIGN.md §9)::

    make_queue(...) + support_core_step(...)   ->  svc.new_burst() ops + svc.commit(...)
    resp.blocks[B:2*B], resp.status[2*B:]      ->  res.blocks_for(ticket), res.ok_for(ticket)
    _gated_support_core_step(...)              ->  svc.commit(..., gated=True)
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Union

import jax.numpy as jnp
from jax import lax

from ..core.freelist import FreeListState
from ..core.hmq import schedule
from ..core.packets import (FREE_ALL, NO_BLOCK, OP_FREE, OP_MALLOC,
                            OP_MALLOC_RUN, OP_NOP, OP_REFILL, RequestQueue,
                            ResponseQueue)
from ..core.support_core import ALLOC_BACKENDS, StepStats
from .policies import AllocatorPolicy, get_policy


#: Separator between an engine namespace and the base tenant name
#: (``"e0/kv_pages"``): one shared service can carry N engines' disjoint
#: tenant sets and still roll telemetry up by base name (DESIGN.md §10).
NAMESPACE_SEP = "/"


class TenantHandle(NamedTuple):
    """A registered client of the support-core (maps to one size class).

    ``quota`` is the hard per-tenant block budget — identical to the class
    capacity, because segregated per-class metadata *is* the quota
    mechanism: a tenant's mallocs draw only on its own pool, so no burst
    mix can let one tenant starve another's blocks.
    """

    name: str
    #: Size-class index.  A Python int on the host side; inside a
    #: tenant-agnostic jitted step (DESIGN.md §13) handles carry TRACED
    #: int32 scalars instead, so one executable serves every shard's
    #: namespaced classes.  Everything downstream (builders, HMQ schedule,
    #: policies, the fused kernel) treats it as data, never as a shape.
    size_class: Union[int, jnp.ndarray]
    capacity: int

    @property
    def quota(self) -> int:
        return self.capacity

    @property
    def namespace(self) -> str:
        """Engine namespace prefix (empty for un-namespaced tenants)."""
        return self.name.rsplit(NAMESPACE_SEP, 1)[0] \
            if NAMESPACE_SEP in self.name else ""

    @property
    def base_name(self) -> str:
        """Tenant name with the engine namespace stripped (rollup key)."""
        return self.name.rsplit(NAMESPACE_SEP, 1)[-1]


class Ticket(NamedTuple):
    """Handle to a contiguous run of burst slots, resolved after commit."""

    start: int
    count: int


class TenantStats(NamedTuple):
    """Per-tenant (== per size class) breakdown of one burst, all ``[C]``."""

    mallocs: jnp.ndarray          # malloc/refill packets per tenant
    failed: jnp.ndarray           # of those, not fully served
    blocks_allocated: jnp.ndarray
    blocks_freed: jnp.ndarray
    used: jnp.ndarray             # post-step occupancy (quota consumption)


class BurstStats(NamedTuple):
    """Telemetry for one committed burst: aggregate + per-tenant.

    ``queue_live`` / ``queue_capacity`` measure burst occupancy — how full
    the fixed-capacity HMQ batch actually was (the multi-tenant packing
    metric).
    """

    core: StepStats
    per_tenant: TenantStats
    queue_live: jnp.ndarray       # non-NOP slots in the built queue
    queue_capacity: jnp.ndarray   # static queue capacity (as a traced const)

    # forwarders so BurstStats reads like the StepStats it extends
    @property
    def mallocs(self):
        return self.core.mallocs

    @property
    def frees(self):
        return self.core.frees

    @property
    def failed(self):
        return self.core.failed

    @property
    def blocks_allocated(self):
        return self.core.blocks_allocated

    @property
    def blocks_freed(self):
        return self.core.blocks_freed


class BurstResult(NamedTuple):
    """One committed burst's responses, resolved through tickets."""

    blocks: jnp.ndarray           # [Q, R] caller-order granted block ids
    status: jnp.ndarray           # [Q]    caller-order status (1 = served)
    stats: BurstStats
    live: jnp.ndarray             # 0/1 — whether the support-core step ran

    def blocks_for(self, ticket: Ticket) -> jnp.ndarray:
        """``[count, R]`` blocks for the ticket's slots (caller order)."""
        return self.blocks[ticket.start:ticket.start + ticket.count]

    def ok_for(self, ticket: Ticket) -> jnp.ndarray:
        """``[count]`` bool success per ticket slot."""
        return self.status[ticket.start:ticket.start + ticket.count] == 1


def _as_lane_vector(lane) -> jnp.ndarray:
    lane = jnp.asarray(lane, jnp.int32)
    return lane.reshape(1) if lane.ndim == 0 else lane


class BurstBuilder:
    """Stages typed allocator ops for one HMQ burst.

    Every op takes a scalar or ``[B]`` vector of lanes (one packet slot per
    lane) plus an optional ``where`` mask — masked-out slots become
    ``OP_NOP`` packets, which keeps shapes static for jit while letting the
    op be conditional per lane (the decode path's bread and butter).
    Returns a :class:`Ticket` for post-commit resolution.  Slot order is
    insertion order == response order; the HMQ schedule permutation is
    internal to the service.
    """

    def __init__(self, service: "AllocService"):
        self._service = service
        self._ops: list[jnp.ndarray] = []
        self._lanes: list[jnp.ndarray] = []
        self._classes: list[jnp.ndarray] = []
        self._args: list[jnp.ndarray] = []
        self._size = 0

    @property
    def size(self) -> int:
        """Number of staged packet slots (the burst's queue capacity)."""
        return self._size

    def _append(self, op: int, tenant: TenantHandle, lane, arg, where
                ) -> Ticket:
        lanes = _as_lane_vector(lane)
        n = lanes.shape[0]
        args = jnp.broadcast_to(jnp.asarray(arg, jnp.int32), (n,))
        ops = jnp.full((n,), op, jnp.int32)
        if where is not None:
            mask = jnp.broadcast_to(jnp.asarray(where, bool), (n,))
            ops = jnp.where(mask, ops, OP_NOP)
            args = jnp.where(mask, args, 0)
        self._ops.append(ops)
        self._lanes.append(lanes)
        # broadcast, not fill: ``size_class`` may be a traced int32 scalar
        # (the tenant-agnostic decode step, DESIGN.md §13) and must enter
        # the queue as data rather than a trace-time constant
        self._classes.append(jnp.broadcast_to(
            jnp.asarray(tenant.size_class, jnp.int32), (n,)))
        self._args.append(args)
        ticket = Ticket(self._size, n)
        self._size += n
        return ticket

    def malloc(self, tenant: TenantHandle, lane, n=1, where=None) -> Ticket:
        """Request ``n`` blocks of ``tenant`` per lane (on the critical
        path: scheduled before refills and frees)."""
        return self._append(OP_MALLOC, tenant, lane, n, where)

    def refill(self, tenant: TenantHandle, lane, n, where=None) -> Ticket:
        """Speculative bulk malloc at refill priority — scheduled after
        every plain malloc, so it can never starve an on-path allocation."""
        return self._append(OP_REFILL, tenant, lane, n, where)

    def malloc_run(self, tenant: TenantHandle, lane, n=1, where=None
                   ) -> Ticket:
        """Malloc with a CONTIGUITY hint: same grant/fail semantics and
        priority as :meth:`malloc`, but a run-aware policy (``buddy``,
        DESIGN.md §15) places the ``n`` blocks as one aligned
        power-of-two run when the free map has one.  When the service's
        resolved policy has no run support the packet is emitted as a
        plain ``OP_MALLOC`` — the hint lowers at staging time, so the
        fused free-list kernel never sees an opcode it does not know."""
        policy = self._service.resolve_policy()
        op = OP_MALLOC_RUN if getattr(policy, "supports_runs", False) \
            else OP_MALLOC
        return self._append(op, tenant, lane, n, where)

    def free(self, tenant: TenantHandle, lane, block, where=None) -> Ticket:
        """Return single block ids (deferred: allocatable next burst).

        Slots whose ``block`` is negative (e.g. a ``NO_BLOCK`` table entry)
        become NOPs: the packet encoding reserves negative args for
        ``FREE_ALL``, so without this guard a stray -1 would silently free
        the lane's ENTIRE holding.  Use :meth:`free_all` to request that
        explicitly.
        """
        lanes = _as_lane_vector(lane)
        n = lanes.shape[0]
        valid = jnp.broadcast_to(jnp.asarray(block, jnp.int32), (n,)) >= 0
        if where is not None:
            valid = valid & jnp.broadcast_to(jnp.asarray(where, bool), (n,))
        return self._append(OP_FREE, tenant, lanes, block, valid)

    def free_all(self, tenant: TenantHandle, lane, where=None) -> Ticket:
        """Free every block of ``tenant`` the lane owns (lane release)."""
        return self._append(OP_FREE, tenant, lane, FREE_ALL, where)

    def build_queue(self, capacity: Optional[int] = None) -> RequestQueue:
        """Concatenate staged slots into one fixed-format request queue."""
        if not self._size:
            raise ValueError("empty burst: stage at least one op (or skip "
                             "the commit entirely)")
        pad = 0 if capacity is None else capacity - self._size
        if pad < 0:
            raise ValueError(
                f"burst of {self._size} slots exceeds the queue capacity "
                f"{capacity}")
        z = [jnp.zeros((pad,), jnp.int32)] if pad else []
        return RequestQueue(
            op=jnp.concatenate(self._ops + z),
            lane=jnp.concatenate(self._lanes + z),
            size_class=jnp.concatenate(self._classes + z),
            arg=jnp.concatenate(self._args + z),
        )


class AllocService:
    """The support-core's client API: tenants in, tickets out.

    Construct once per allocator instance (host side), ``register_tenant``
    each client, then drive bursts from anywhere — including inside jit —
    via :meth:`new_burst` + :meth:`commit`.  ``policy`` / ``backend`` left
    ``None`` resolve the ``REPRO_ALLOC_POLICY`` / ``REPRO_ALLOC_BACKEND``
    env knobs at commit (trace) time, exactly like the deprecated
    ``support_core_step`` wrapper did.
    """

    def __init__(self, policy: Optional[str] = None,
                 backend: Optional[str] = None):
        self._policy_name = policy
        self._backend = backend
        self._tenants: dict[str, TenantHandle] = {}
        #: Optional allocator-op trace recorder (``repro.loadgen.trace``).
        #: When set, every eager commit / retag / refcount-bump is appended
        #: to the recorder's event stream in state-mutation order; traced
        #: (in-jit) commits are counted but not serialized — see
        #: DESIGN.md §14 for why ``decode_bursts == 0`` certifies the
        #: trace complete anyway.
        self.recorder = None

    # ---------------- tenants ----------------

    def register_tenant(self, name: str, capacity: int) -> TenantHandle:
        """Add a named client; its quota is ``capacity`` blocks (hard
        isolation — the tenant's own size class is its entire pool)."""
        if name in self._tenants:
            raise ValueError(f"tenant {name!r} already registered")
        if capacity <= 0:
            raise ValueError(f"tenant {name!r}: capacity must be positive")
        handle = TenantHandle(name=name, size_class=len(self._tenants),
                              capacity=int(capacity))
        self._tenants[name] = handle
        return handle

    def register_tenants(self, spec: Sequence[tuple[str, int]],
                         namespace: str = "") -> tuple[TenantHandle, ...]:
        """Grow the tenant table by a whole client set at once.

        ``spec`` is ``[(base_name, capacity), ...]``; a non-empty
        ``namespace`` prefixes every name (``"e0" -> "e0/kv_pages"``) so N
        engine shards register DISJOINT tenant sets on ONE service — the
        multi-engine sharding scheme (DESIGN.md §10).  Registration order
        fixes the size-class indices, exactly like single registration.
        """
        if namespace and NAMESPACE_SEP in namespace:
            raise ValueError(
                f"namespace {namespace!r} must not contain {NAMESPACE_SEP!r}")
        prefix = f"{namespace}{NAMESPACE_SEP}" if namespace else ""
        return tuple(self.register_tenant(f"{prefix}{name}", capacity)
                     for name, capacity in spec)

    def tenant(self, name: str, namespace: str = "") -> TenantHandle:
        if namespace:
            name = f"{namespace}{NAMESPACE_SEP}{name}"
        try:
            return self._tenants[name]
        except KeyError:
            raise KeyError(
                f"unknown tenant {name!r}; registered: "
                f"{list(self._tenants)}") from None

    def namespace_tenants(self, namespace: str) -> tuple[TenantHandle, ...]:
        """All tenants registered under one engine namespace."""
        prefix = f"{namespace}{NAMESPACE_SEP}"
        return tuple(t for t in self.tenants if t.name.startswith(prefix))

    @property
    def namespaces(self) -> tuple[str, ...]:
        """Distinct engine namespaces, in registration order."""
        seen: dict[str, None] = {}
        for t in self.tenants:
            if t.namespace:
                seen.setdefault(t.namespace, None)
        return tuple(seen)

    @property
    def tenants(self) -> tuple[TenantHandle, ...]:
        return tuple(self._tenants.values())

    @property
    def num_classes(self) -> int:
        return len(self._tenants)

    def init_state(self, policy: Optional[str] = None) -> FreeListState:
        """Fresh segregated metadata covering every registered tenant.

        ``policy`` must name the same policy later bursts will run (it may
        have a custom ``init``); ``None`` falls back to the service's
        policy / the env knob, like :meth:`commit`.
        """
        if not self._tenants:
            raise ValueError("register at least one tenant before init_state")
        return self.resolve_policy(policy).init(
            [t.capacity for t in self.tenants])

    # ---------------- policy / backend resolution ----------------

    def resolve_policy(self, policy: Optional[str] = None) -> AllocatorPolicy:
        name = policy if policy is not None else self._policy_name
        if name is None:
            from ..perf_flags import current_flags
            name = current_flags().alloc_policy
        return get_policy(name)

    def resolve_backend(self, backend: Optional[str] = None,
                        policy: Optional[AllocatorPolicy] = None) -> str:
        """Resolve the backend name (arg > service > env).

        A name is known if it belongs to the standard trio
        (``ALLOC_BACKENDS``) or to the resolved policy's own ``backends`` —
        a policy registered via ``register_policy`` may bring its own
        backend names.
        """
        backend = backend if backend is not None else self._backend
        if backend is None:
            from ..perf_flags import current_flags
            backend = current_flags().alloc_backend
        known = set(ALLOC_BACKENDS) | set(policy.backends if policy else ())
        if backend not in known:
            raise ValueError(
                f"unknown alloc backend {backend!r}; expected one of "
                f"{sorted(known)}")
        return backend

    # ---------------- bursts ----------------

    def new_burst(self) -> BurstBuilder:
        return BurstBuilder(self)

    def retag_blocks(
        self,
        state: FreeListState,
        tenant: TenantHandle,
        blocks,
        new_owner: int,
    ) -> FreeListState:
        """Control-plane ownership transfer of live blocks (no HMQ traffic).

        Rewrites ``owner[class, block]`` for already-allocated blocks — the
        demotion primitive behind the KV prefix cache (DESIGN.md §11): a
        completed lane's pages are retagged to the cache's synthetic owner
        so the lane's FREE_ALL (which matches ``owner == lane``) skips
        them, while single OP_FREEs (owner-agnostic) can still reclaim
        them later.  Allocation counters and ``used`` are untouched: the
        pages stay charged against the tenant's quota, which is exactly
        what keeps admission page-budget math honest while the cache holds
        them.  Host-side metadata op; never touches page payloads.
        """
        blocks = jnp.asarray(blocks, jnp.int32)
        if blocks.size == 0:
            return state
        if self.recorder is not None:
            self.recorder.on_retag(tenant.size_class, blocks, new_owner)
        owner = state.owner.at[tenant.size_class, blocks].set(
            jnp.int32(new_owner), mode="drop")
        return state._replace(owner=owner)

    def bump_refcounts(
        self,
        state: FreeListState,
        tenant: TenantHandle,
        blocks,
        delta: int = 1,
    ) -> FreeListState:
        """Control-plane refcount adjustment of live blocks (no HMQ traffic).

        The aliasing primitive behind zero-copy prefix-cache hits
        (DESIGN.md §12): splicing a cache-owned page into a lane's block
        table bumps ``refcount[class, block]`` by one per new reference, so
        the page only returns to the central stack once EVERY referencing
        lane's OP_FREE decrement and the cache's own release have landed.
        Duplicate ids in ``blocks`` accumulate (``delta`` each).  Owner map,
        counters, and ``used`` are untouched — an aliased page is one
        physical page, charged once.  Host-side metadata op; never touches
        page payloads.
        """
        blocks = jnp.asarray(blocks, jnp.int32)
        if blocks.size == 0:
            return state
        if self.recorder is not None:
            self.recorder.on_bump(tenant.size_class, blocks, delta)
        refcount = state.refcount.at[tenant.size_class, blocks].add(
            jnp.int32(delta), mode="drop")
        return state._replace(refcount=refcount)

    def commit(
        self,
        state: FreeListState,
        burst: Union[BurstBuilder, RequestQueue],
        max_blocks_per_req: int = 1,
        backend: Optional[str] = None,
        policy: Optional[str] = None,
        gated: bool = False,
    ) -> tuple[FreeListState, BurstResult]:
        """Run one support-core step over the staged burst.

        ``gated=True`` wraps the step in a ``lax.cond`` on any-live-packet,
        so an all-NOP burst costs zero central-allocator work (bit-identical
        state, all tickets resolve failed/empty) — the fast path stash-served
        decode steps rely on (DESIGN.md §7).
        """
        queue = burst.build_queue() if isinstance(burst, BurstBuilder) \
            else burst
        if self.recorder is not None:
            self.recorder.on_commit(queue, max_blocks_per_req)
        if self._tenants and state.num_classes != self.num_classes:
            # Tenant-table growth after init_state (or a state from another
            # service) would silently mis-route classes; fail loudly instead.
            # (A tenant-LESS service is the legacy raw-queue bridge
            # (``AllocService.step``) whose callers own their class layout;
            # it stays unguarded.)
            raise ValueError(
                f"allocator state carries {state.num_classes} size classes "
                f"but this service has {self.num_classes} registered tenants "
                f"({list(self._tenants)}); register every tenant BEFORE "
                f"init_state and commit against the matching state")
        policy = self.resolve_policy(policy)
        backend = self.resolve_backend(backend, policy=policy)
        if backend not in policy.backends:
            raise ValueError(
                f"policy {policy.name!r} does not support backend "
                f"{backend!r} (supported: {policy.backends})")

        Q, R = queue.capacity, max_blocks_per_req
        C = state.num_classes
        live = jnp.any(queue.op != OP_NOP)

        def run(_):
            return self._scheduled_step(policy, backend, state, queue, R)

        def skip(_):
            z = jnp.zeros((), jnp.int32)
            zc = jnp.zeros((C,), jnp.int32)
            return (state,
                    jnp.full((Q, R), NO_BLOCK, jnp.int32),
                    jnp.zeros((Q,), jnp.int32),
                    StepStats(z, z, z, z, z),
                    TenantStats(zc, zc, zc, zc, state.used))

        if gated:
            new_state, blocks, status, core, per_tenant = lax.cond(
                live, run, skip, 0)
        else:
            new_state, blocks, status, core, per_tenant = run(0)

        stats = BurstStats(
            core=core,
            per_tenant=per_tenant,
            queue_live=jnp.sum(queue.op != OP_NOP).astype(jnp.int32),
            queue_capacity=jnp.int32(Q),
        )
        return new_state, BurstResult(blocks=blocks, status=status,
                                      stats=stats,
                                      live=live.astype(jnp.int32))

    def _scheduled_step(self, policy, backend, state, queue, R):
        """Schedule + policy step + caller-order routing + stats.

        Everything outside ``policy.step_scheduled`` is policy- and
        backend-independent, so identical backend outputs give identical
        responses and telemetry (the bit-identity the differential suites
        prove old-vs-new and jnp-vs-kernel).
        """
        C = state.num_classes
        sched, unperm = schedule(queue)
        new_state, blocks, ok = policy.step_scheduled(state, sched, R, backend)

        is_malloc = ((sched.op == OP_MALLOC) | (sched.op == OP_REFILL)
                     | (sched.op == OP_MALLOC_RUN))
        is_free = sched.op == OP_FREE
        status_sched = jnp.where(is_malloc, ok,
                                 (sched.op != OP_NOP).astype(jnp.int32))
        core = StepStats(
            mallocs=jnp.sum(is_malloc).astype(jnp.int32),
            frees=jnp.sum(is_free).astype(jnp.int32),
            failed=jnp.sum(is_malloc & (ok == 0)).astype(jnp.int32),
            blocks_allocated=jnp.sum(blocks != NO_BLOCK).astype(jnp.int32),
            blocks_freed=jnp.sum(new_state.free_count - state.free_count)
            .astype(jnp.int32),
        )
        cls = jnp.clip(sched.size_class, 0, C - 1)
        onehot = (jnp.arange(C, dtype=jnp.int32)[None, :]
                  == cls[:, None]).astype(jnp.int32)            # [Q, C]
        per_tenant = TenantStats(
            mallocs=jnp.sum(is_malloc[:, None] * onehot, axis=0)
            .astype(jnp.int32),
            failed=jnp.sum((is_malloc & (ok == 0))[:, None] * onehot, axis=0)
            .astype(jnp.int32),
            blocks_allocated=jnp.sum(
                jnp.sum(blocks != NO_BLOCK, axis=1)[:, None] * onehot, axis=0)
            .astype(jnp.int32),
            blocks_freed=(new_state.free_count - state.free_count)
            .astype(jnp.int32),
            used=new_state.used,
        )
        return (new_state, blocks[unperm], status_sched[unperm], core,
                per_tenant)

    # ---------------- legacy bridge ----------------

    def step(self, state: FreeListState, queue: RequestQueue,
             max_blocks_per_req: int = 1, backend: Optional[str] = None,
             policy: Optional[str] = None,
             ) -> tuple[FreeListState, ResponseQueue, BurstStats]:
        """One raw-queue burst in the historical ``support_core_step``
        return shape (the raw-queue bridge; that wrapper is gone — tests
        and benchmarks that drive hand-built queues call this instead)."""
        new_state, res = self.commit(state, queue,
                                     max_blocks_per_req=max_blocks_per_req,
                                     backend=backend, policy=policy)
        return new_state, ResponseQueue(blocks=res.blocks, status=res.status), \
            res.stats

    # ---------------- host-side reporting ----------------

    def tenant_report(self, state: FreeListState,
                      tenants: Optional[Sequence[TenantHandle]] = None,
                      ) -> dict[str, dict]:
        """Host-side per-tenant occupancy/quota/counter snapshot
        (telemetry + readable quota-bug errors; not jittable).

        ``tenants`` restricts the report to a subset of handles — an engine
        shard passes its own tenant set so its report never mixes in the
        other shards sharing the service.
        """
        import numpy as np
        used = np.asarray(state.used)
        peak = np.asarray(state.peak_used)
        allocs = np.asarray(state.alloc_count)
        frees = np.asarray(state.free_count)
        fails = np.asarray(state.fail_count)
        out = {}
        for t in (self.tenants if tenants is None else tenants):
            c = t.size_class
            out[t.name] = {
                "size_class": c,
                "quota": t.quota,
                "used": int(used[c]),
                "peak_used": int(peak[c]),
                "alloc_count": int(allocs[c]),
                "free_count": int(frees[c]),
                "fail_count": int(fails[c]),
            }
        return out

    def rollup_report(self, state: FreeListState) -> dict[str, dict]:
        """Cross-engine per-tenant rollup: aggregate the report by BASE
        tenant name across every namespace sharing this service.

        ``"e0/kv_pages"`` + ``"e1/kv_pages"`` -> one ``"kv_pages"`` row with
        summed quota/used/counters and an ``engines`` count — the
        many-clients-one-core view of the multi-engine deployment
        (DESIGN.md §10).
        """
        out: dict[str, dict] = {}
        for t, rep in zip(self.tenants,
                          self.tenant_report(state).values()):
            d = out.setdefault(t.base_name, {
                "engines": 0, "quota": 0, "used": 0, "peak_used": 0,
                "alloc_count": 0, "free_count": 0, "fail_count": 0,
            })
            d["engines"] += 1
            for k in ("quota", "used", "peak_used", "alloc_count",
                      "free_count", "fail_count"):
                d[k] += rep[k]
        return out

    def fragmentation_report(self, state: FreeListState,
                             tenants: Optional[Sequence[TenantHandle]] = None,
                             ) -> dict[str, dict]:
        """Host-side per-tenant external-fragmentation snapshot
        (DESIGN.md §15): free pages, largest contiguous / aligned free
        run, ``external_frag`` in [0, 1], and the cumulative buddy
        split/merge counters.  Same subset convention as
        :meth:`tenant_report`; not jittable."""
        from ..core.freelist import fragmentation_report
        full = fragmentation_report(state, tenant_names=self.tenant_names())
        names = [t.name for t in (self.tenants if tenants is None
                                  else tenants)]
        return {n: full[n] for n in names}

    def tenant_names(self) -> tuple[str, ...]:
        return tuple(self._tenants)


def empty_burst_stats(num_classes: int,
                      used: Optional[jnp.ndarray] = None) -> BurstStats:
    """All-zero BurstStats for code paths that issue no burst at all
    (shape-compatible with a real one for ``lax.cond`` branches)."""
    z = jnp.zeros((), jnp.int32)
    zc = jnp.zeros((num_classes,), jnp.int32)
    return BurstStats(
        core=StepStats(z, z, z, z, z),
        per_tenant=TenantStats(zc, zc, zc, zc,
                               used if used is not None else zc),
        queue_live=z,
        queue_capacity=z,
    )
