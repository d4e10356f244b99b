"""Fused support-core step — the ENTIRE HMQ burst as one Pallas kernel.

The paper's support-core is a deliberately *lightweight* core: integer-only,
no FP/vector units (§2.4), with the whole segregated metadata in its private
L1 (§5.1).  The TPU-native analogue is a single VPU-only kernel — zero MXU
work — with ``free_stack``, ``owner`` and ``refcount`` resident in VMEM for
the whole burst, playing the role of the support-core's L1, and the queue
plus the [C] counters in SMEM, read and written by the TPU's scalar unit:
one launch services a whole scheduled HMQ batch, and the metadata makes
exactly one HBM→VMEM→HBM round trip per burst instead of one per XLA op
(the ``"jnp"`` backend's scan + gathers + scatters each re-touch HBM).

Scope (DESIGN.md §8): everything in
:func:`repro.core.support_core._step_scheduled_jnp` for an
already-``hmq.schedule``d queue —

  * sequential-skip malloc grants: a scalar ``fori_loop`` over the queue
    whose only state is the per-class stack top (SMEM), so a failed request
    consumes nothing for its successors;
  * the stack gather + owner-map/refcount update, one 128-lane row at a
    time for each granted block;
  * single-block frees (row read-modify-write counts) and the FREE_ALL
    owner sweep (a masked OR over the class's rows per FREE_ALL packet —
    the host path's sorted-lane-list binary search exists to avoid
    materializing [Q, C, N] in HBM, which a VMEM-resident kernel never
    does);
  * the deferred-free compaction + stack append: per 128-id row, the
    in-row prefix count and the rank-select of returned ids are [128, 128]
    compare-and-sum passes, written at the running stack offset;
  * all counters (used / peak_used / alloc_count / free_count / fail_count).

HMQ scheduling (the priority/round-robin sort) and response unpermutation
stay in the host-side dispatcher — they are queue bookkeeping, not metadata
mutation.

Layout: the wrapper pads N up to a multiple of ``_TILE`` ids and views each
``[C, N]`` plane as ``[C, T, 128]`` (T rows of 128 ids), so every vector op
is a whole number of (8, 128) int32 tiles and no op needs a gather, a
scatter or a scan, none of which Mosaic lowers here.  Padded ids carry
owner -1 and are never granted (they sit above every stack top), so they
never change state.  Frees are refcount decrements (DESIGN.md §12): the
freed-id compaction and owner clear apply only to blocks whose refcount
reaches 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.packets import (FREE_ALL, NO_BLOCK, OP_FREE, OP_MALLOC,
                             OP_MALLOC_RUN, OP_REFILL)

_LANES = 128                 # ids per row (the vreg lane width)
_SUB = 8                     # rows per vector chunk (the int32 sublane count)
_TILE = _LANES * _SUB        # ids per chunk; N is padded to a multiple
_FA_SHIFT = 20               # FREE_ALL hit bit in the free-count scratch
_COUNT_MASK = (1 << _FA_SHIFT) - 1


def _lane_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)


def _read_id(ref, c, p):
    """Scalar ``ref[c, p // 128, p % 128]`` via one row load + masked sum."""
    row = ref[c, pl.ds(p // _LANES, 1), :]                  # [1, 128]
    return jnp.sum(jnp.where(_lane_iota() == p % _LANES, row, 0))


def _write_id(ref, c, p, value):
    """``ref[c, p // 128, p % 128] = value`` as one row read-modify-write."""
    r = pl.ds(p // _LANES, 1)
    ref[c, r, :] = jnp.where(_lane_iota() == p % _LANES, value, ref[c, r, :])


def _kernel(
    # --- scheduled queue (SMEM, [Q] int32): runtime DATA, so namespaced
    # size-class ids arrive per launch and one compiled kernel serves every
    # engine shard (DESIGN.md §13) ---
    op_ref, lane_ref, cls_ref, arg_ref,
    # --- counters (SMEM, [C] int32) ---
    top_ref, alloc_cnt_ref, free_cnt_ref, fail_cnt_ref, used_ref, peak_ref,
    # --- segregated metadata planes (VMEM, [C, T, 128] int32) ---
    stack_ref, owner_ref, refcount_ref,
    # --- outputs: planes (VMEM), counters (SMEM), responses (SMEM) ---
    new_stack_ref, new_owner_ref, new_refcount_ref,
    new_top_ref, new_alloc_ref, new_free_ref, new_fail_ref, new_used_ref,
    new_peak_ref,
    blocks_ref,     # [Q * R] int32, scheduled order, row-major
    ok_ref,         # [Q] int32
    # --- scratch ---
    cnt_ref,        # VMEM [C, T, 128]: per-block reference drops, then the
    #                 returned-block mask
    *,
    num_blocks: int,
    max_per_req: int,
):
    C, T, _ = stack_ref.shape
    Q = op_ref.shape[0]
    R = max_per_req
    N = num_blocks
    chunks = T // _SUB

    def chunk(k):
        return pl.ds(pl.multiple_of(k * _SUB, _SUB), _SUB)

    # ---- working copies: every mutation below lands in the outputs ----
    for c in range(C):
        def copy_body(k, carry, c=c):
            rows = chunk(k)
            new_stack_ref[c, rows, :] = stack_ref[c, rows, :]
            new_owner_ref[c, rows, :] = owner_ref[c, rows, :]
            new_refcount_ref[c, rows, :] = refcount_ref[c, rows, :]
            cnt_ref[c, rows, :] = jnp.zeros((_SUB, _LANES), jnp.int32)
            return carry
        jax.lax.fori_loop(0, chunks, copy_body, 0)
        new_top_ref[c] = top_ref[c]
        new_fail_ref[c] = fail_cnt_ref[c]

    def clear_body(i, carry):
        blocks_ref[i] = jnp.int32(NO_BLOCK)
        return carry
    jax.lax.fori_loop(0, Q * R, clear_body, 0)

    # ---- malloc phase: sequential-skip grants served from the pre-step
    # stack.  ``new_top[c]`` is the class's unconsumed top: a request of
    # want w is granted iff w <= new_top[c], and then takes the w ids just
    # below it (request i's my_goff == top[c] - new_top[c]). ----
    def grant_body(i, carry):
        op = op_ref[i]
        c = jnp.clip(cls_ref[i], 0, C - 1)
        arg = arg_ref[i]
        is_m = (op == OP_MALLOC) | (op == OP_REFILL) | (op == OP_MALLOC_RUN)
        want = jnp.where(is_m, jnp.maximum(arg, 0), 0)
        want = jnp.where(want <= R, want, 0)                # overwide -> fail
        avail = new_top_ref[c]
        ok = is_m & (want > 0) & (want <= avail)
        ok_ref[i] = ok.astype(jnp.int32)
        new_fail_ref[c] = new_fail_ref[c] + (is_m & ~ok).astype(jnp.int32)

        @pl.when(ok)
        def _grant():
            new_top_ref[c] = avail - want
            lane = lane_ref[i]

            def take(j, carry):
                blk = _read_id(stack_ref, c, avail - 1 - j)
                blocks_ref[i * R + j] = blk

                # out-of-range ids (a corrupt stack) are dropped, as the
                # reference's mode="drop" scatter drops them
                @pl.when((blk >= 0) & (blk < N))
                def _own():
                    _write_id(new_owner_ref, c, blk, lane)
                    # fresh grants carry exactly one reference (§12)
                    _write_id(new_refcount_ref, c, blk, 1)
                return carry
            jax.lax.fori_loop(0, want, take, 0)
        return carry
    jax.lax.fori_loop(0, Q, grant_body, 0)

    # ---- free phase (deferred: frees cannot serve this step's mallocs).
    # Each single-block free drops one reference; a FREE_ALL sets the hit
    # bit of every block its lane owns (at most 1 per block, idempotent).
    # The post-alloc owner map is used, so a block granted this step can be
    # freed this step. ----
    def free_body(i, carry):
        op = op_ref[i]
        c = jnp.clip(cls_ref[i], 0, C - 1)
        arg = arg_ref[i]
        is_free = op == OP_FREE

        @pl.when(is_free & (arg >= 0) & (arg < N))
        def _single():
            r = pl.ds(arg // _LANES, 1)
            cnt_ref[c, r, :] = cnt_ref[c, r, :] + (
                _lane_iota() == arg % _LANES).astype(jnp.int32)

        @pl.when(is_free & (arg == FREE_ALL))
        def _free_all():
            lane = lane_ref[i]

            def sweep(k, carry):
                rows = chunk(k)
                hit = (new_owner_ref[c, rows, :] == lane).astype(jnp.int32)
                cnt_ref[c, rows, :] = cnt_ref[c, rows, :] | (hit << _FA_SHIFT)
                return carry
            jax.lax.fori_loop(0, chunks, sweep, 0)
        return carry
    jax.lax.fori_loop(0, Q, free_body, 0)

    # ---- refcounted release (§12): each matched free decrements; a block
    # returns to the stack (and drops its owner) only at refcount 0.  Only
    # currently-owned blocks free (a free of an unowned block is a nop). ----
    n_iota = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    for c in range(C):
        def release_body(k, carry, c=c):
            rows = chunk(k)
            owner = new_owner_ref[c, rows, :]
            cnt = cnt_ref[c, rows, :]
            drops = ((cnt & _COUNT_MASK) + (cnt >> _FA_SHIFT)) \
                * (owner >= 0).astype(jnp.int32)
            dec = new_refcount_ref[c, rows, :] - drops
            ret = (drops > 0) & (dec <= 0)
            new_refcount_ref[c, rows, :] = jnp.maximum(dec, 0)
            new_owner_ref[c, rows, :] = jnp.where(ret, -1, owner)
            cnt_ref[c, rows, :] = ret.astype(jnp.int32)
            return carry
        jax.lax.fori_loop(0, chunks, release_body, 0)

        # Append the returned ids in ascending order at the post-alloc top:
        # row t's k returned ids go to stack positions [d, d + k), d = top +
        # (ids returned by earlier rows).  Within the row, cum[n] counts the
        # returned ids <= n, and the id of rank q is #{n : cum[n] <= q}.
        top_after = new_top_ref[c]

        def append_body(t, base, c=c):
            m = cnt_ref[c, pl.ds(t, 1), :]                          # [1, 128]
            k = jnp.sum(m)

            @pl.when(k > 0)
            def _append():
                cum = jnp.sum(jnp.where(r_iota <= n_iota, m, 0), axis=1,
                              keepdims=True)                         # [128, 1]
                d = top_after + base
                for s in range(2):            # the k ids span <= 2 rows
                    q = d // _LANES + s
                    rank = q * _LANES + _lane_iota() - d             # [1, 128]
                    ids = t * _LANES + jnp.sum(
                        (cum <= rank).astype(jnp.int32), axis=0,
                        keepdims=True)
                    put = (rank >= 0) & (rank < k)

                    @pl.when((q < T) & jnp.any(put))
                    def _put():
                        rq = pl.ds(q, 1)
                        new_stack_ref[c, rq, :] = jnp.where(
                            put, ids, new_stack_ref[c, rq, :])
            return base + k
        freed = jax.lax.fori_loop(0, T, append_body, jnp.int32(0))

        # ---- counters ----
        taken = top_ref[c] - top_after
        used_after_alloc = used_ref[c] + taken
        new_peak_ref[c] = jnp.maximum(peak_ref[c], used_after_alloc)
        new_used_ref[c] = used_after_alloc - freed
        new_top_ref[c] = top_after + freed
        new_alloc_ref[c] = alloc_cnt_ref[c] + taken
        new_free_ref[c] = free_cnt_ref[c] + freed


def fused_step_kernel(
    op: jnp.ndarray,          # [Q] int32 — SCHEDULED queue
    lane: jnp.ndarray,        # [Q] int32
    size_class: jnp.ndarray,  # [Q] int32
    arg: jnp.ndarray,         # [Q] int32
    free_stack: jnp.ndarray,  # [C, N] int32
    free_top: jnp.ndarray,    # [C] int32
    owner: jnp.ndarray,       # [C, N] int32
    refcount: jnp.ndarray,    # [C, N] int32
    alloc_count: jnp.ndarray,  # [C] int32
    free_count: jnp.ndarray,   # [C] int32
    fail_count: jnp.ndarray,   # [C] int32
    used: jnp.ndarray,         # [C] int32
    peak_used: jnp.ndarray,    # [C] int32
    *,
    max_per_req: int,
    interpret: bool = False,
):
    """One fused launch for a whole scheduled HMQ burst.

    The four queue vectors and the [C] counters ride in SMEM; being runtime
    operands rather than compile-time constants, the queue carries whatever
    (possibly traced) namespaced class ids the burst staged, so ONE compiled
    kernel serves every engine shard (DESIGN.md §13).

    Returns ``(new_stack [C,N], new_top [C], new_owner [C,N],
    new_refcount [C,N], new_alloc [C], new_free [C], new_fail [C],
    new_used [C], new_peak [C], blocks [Q,R], ok [Q])``.
    """
    Q = op.shape[0]
    C, N = free_stack.shape
    R = max_per_req
    n_pad = -(-N // _TILE) * _TILE
    T = n_pad // _LANES

    def plane(x, fill):
        x = jnp.pad(x.astype(jnp.int32), ((0, 0), (0, n_pad - N)),
                    constant_values=fill)
        return x.reshape(C, T, _LANES)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    plane_shape = jax.ShapeDtypeStruct((C, T, _LANES), jnp.int32)
    c_shape = jax.ShapeDtypeStruct((C,), jnp.int32)
    # The scoped limit covers the scratch plane and row temporaries; XLA
    # places the six operand planes in VMEM outside it (DESIGN.md §8).
    vmem_limit = C * n_pad * 4 + (8 << 20)
    kernel = functools.partial(_kernel, num_blocks=N, max_per_req=R)
    outs = pl.pallas_call(
        kernel,
        in_specs=[smem] * 10 + [vmem] * 3,
        out_specs=[vmem] * 3 + [smem] * 8,
        out_shape=[plane_shape] * 3 + [c_shape] * 6 + [
            jax.ShapeDtypeStruct((Q * R,), jnp.int32),
            jax.ShapeDtypeStruct((Q,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((C, T, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(op.astype(jnp.int32), lane.astype(jnp.int32),
      size_class.astype(jnp.int32), arg.astype(jnp.int32),
      free_top, alloc_count, free_count, fail_count, used, peak_used,
      plane(free_stack, 0), plane(owner, -1), plane(refcount, 0))
    (new_stack, new_owner, new_refcount, new_top, new_alloc, new_free,
     new_fail, new_used, new_peak, blocks, ok) = outs

    def unplane(x):
        return x.reshape(C, n_pad)[:, :N]

    return (unplane(new_stack), new_top, unplane(new_owner),
            unplane(new_refcount), new_alloc, new_free, new_fail, new_used,
            new_peak, blocks.reshape(Q, R), ok)
