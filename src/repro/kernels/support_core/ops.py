"""Jitted public wrapper for the fused support-core burst kernel.

This is the ``kernel`` / ``kernel-interpret`` backend of the *free-list*
:class:`~repro.alloc.policies.AllocatorPolicy` (DESIGN.md §9): clients
reach it through ``AllocService.commit``, which hands every policy an
already-``hmq.schedule``\\ d queue and routes responses backend- and
policy-independently.

NOTE: ``interpret`` defaults to **False** — interpret mode is an explicit
test/CI opt-in (the ``"kernel-interpret"`` backend), never the silent
production path.  ``interpret=False`` requires a TPU (Mosaic) lowering.
"""
from __future__ import annotations

from functools import partial

import jax

from ...core.freelist import FreeListState
from ...core.packets import RequestQueue
from .support_core_kernel import fused_step_kernel


@partial(jax.jit, static_argnames=("max_blocks_per_req", "interpret"))
def support_core_burst(
    state: FreeListState,
    sched: RequestQueue,
    max_blocks_per_req: int = 1,
    interpret: bool = False,
):
    """Run one fused launch over an already-``hmq.schedule``d queue.

    Same contract as :func:`repro.core.support_core._step_scheduled_jnp`
    (the differential reference, re-exported as :mod:`.ref`): returns
    ``(new_state, blocks [Q, R], ok [Q])`` in scheduled order.
    """
    (new_stack, new_top, new_owner, new_refcount, new_alloc, new_free,
     new_fail, new_used, new_peak, blocks, ok) = fused_step_kernel(
        sched.op, sched.lane, sched.size_class, sched.arg,
        state.free_stack, state.free_top, state.owner, state.refcount,
        state.alloc_count, state.free_count, state.fail_count,
        state.used, state.peak_used,
        max_per_req=max_blocks_per_req, interpret=interpret)
    new_state = FreeListState(
        free_stack=new_stack,
        free_top=new_top,
        owner=new_owner,
        refcount=new_refcount,
        capacity=state.capacity,
        alloc_count=new_alloc,
        free_count=new_free,
        fail_count=new_fail,
        used=new_used,
        peak_used=new_peak,
        # the fused free-list kernel never splits/merges runs; the buddy
        # telemetry counters pass through untouched (jnp-only policy)
        split_count=state.split_count,
        merge_count=state.merge_count,
    )
    return new_state, blocks, ok
