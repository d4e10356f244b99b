"""Jitted serving steps: decode (1 token / lane / step) and prefill.

The decode step is the production home of the SpeedMalloc technique: every
step the layer stack reads paged KV via block tables (segregated metadata),
and ends with exactly ONE support-core HMQ batch (`decode_alloc`) carrying
all page mallocs (page-boundary lanes) and frees (slid-out SWA pages).

The decode step only reads the KV pool.  It hands back the new token's K/V
and where it goes (`ServeState.kv_write`), and `write_kv_in_place` writes
them into the donated pool (DESIGN.md §16).
"""
from __future__ import annotations

import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.paged_kv import (KVWrite, PagedKVConfig, PagedKVState,
                             PendingDecodeOps, decode_alloc,
                             empty_decode_stats, init_paged_kv, write_kv)
from ..distributed.hints import use_hints
from ..models.decode import (RecurrentState, decode_hidden, decode_logits,
                             init_recurrent_state)
from ..models.model_zoo import make_paged_config
from ..models.transformer import FULL_WINDOW


class ServeState(NamedTuple):
    paged: PagedKVState
    rec: Optional[RecurrentState]
    tokens: jnp.ndarray                  # [lanes] last sampled token
    enc_out: Optional[jnp.ndarray] = None  # [lanes, F, d] whisper encoder output
    step: jnp.ndarray = None             # scalar int32
    # set only on a decode step's output, whose paged.k_pages/v_pages are
    # None: the K/V the step leaves for ``write_kv_in_place``
    kv_write: Optional[KVWrite] = None


#: The one place the KV pool changes during decode: one step's K/V scattered
#: into both pools, which are donated, so XLA writes them in place.
write_kv_in_place = jax.jit(write_kv, donate_argnums=(0, 1))


def recycle_window(cfg: ArchConfig) -> Optional[int]:
    """Page-recycling window: only when *every* attention layer is windowed."""
    if cfg.attn_pattern == "swa" and cfg.window:
        return cfg.window
    return None


def init_serve_state(
    cfg: ArchConfig,
    kvcfg: PagedKVConfig,
    lanes: int,
    prefilled_len: int = 0,
    dtype=jnp.bfloat16,
) -> ServeState:
    """A serving state with `lanes` active sequences of `prefilled_len` tokens.

    Block tables / free lists are set up as if prefill already admitted the
    sequences (used for decode dry-runs and decode benchmarks; the real
    admission path is `repro.serve.engine`).
    """
    paged = init_paged_kv(kvcfg)
    ps = kvcfg.page_size
    N = kvcfg.num_pages
    n_pages = (prefilled_len + ps) // ps   # incl. page for the next token
    lane_ids = jnp.arange(lanes, dtype=jnp.int32)
    page_grid = jnp.arange(kvcfg.max_pages_per_lane, dtype=jnp.int32)
    window = recycle_window(cfg)
    first_live = 0
    if window is not None:
        first_live = max(0, (prefilled_len - window) // ps)
    live_per_lane = N // lanes
    n_live = min(n_pages - first_live, live_per_lane)
    rank = page_grid[None, :] - first_live
    live = (rank >= 0) & (rank < n_live) & (page_grid[None, :] < n_pages)
    tbl = jnp.where(live, lane_ids[:, None] * live_per_lane + rank, -1)

    # Consistent allocator metadata: page id p is used iff its lane slot is
    # live; free stack holds exactly the unused ids (valid FreeListState).
    pid = jnp.arange(N, dtype=jnp.int32)
    owner_lane = pid // live_per_lane
    used_mask = (owner_lane < lanes) & ((pid % live_per_lane) < n_live)
    used0 = jnp.sum(used_mask).astype(jnp.int32)
    order = jnp.argsort(used_mask, stable=True)       # free ids first
    alloc = paged.alloc
    alloc = alloc._replace(
        free_stack=alloc.free_stack.at[0].set(pid[order]),
        free_top=alloc.free_top.at[0].set(jnp.int32(N) - used0),
        owner=alloc.owner.at[0].set(jnp.where(used_mask, owner_lane, -1)),
        refcount=alloc.refcount.at[0].set(used_mask.astype(jnp.int32)),
        used=alloc.used.at[0].set(used0),
        peak_used=alloc.peak_used.at[0].set(used0),
    )
    paged = paged._replace(
        alloc=alloc,
        block_tables=tbl.astype(jnp.int32),
        seq_lens=jnp.full((lanes,), prefilled_len, jnp.int32),
        active=jnp.ones((lanes,), bool),
    )
    rec = init_recurrent_state(cfg, lanes, dtype)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = jnp.zeros((lanes, cfg.encoder_seq_len, cfg.d_model), dtype)
    return ServeState(paged=paged, rec=rec,
                      tokens=jnp.zeros((lanes,), jnp.int32),
                      enc_out=enc_out, step=jnp.zeros((), jnp.int32))


def abstract_serve_state(cfg: ArchConfig, kvcfg: PagedKVConfig, lanes: int,
                         prefilled_len: int, dtype=jnp.bfloat16):
    """ShapeDtypeStruct serving state (dry-run; no allocation)."""
    return jax.eval_shape(
        lambda: init_serve_state(cfg, kvcfg, lanes, prefilled_len, dtype))


def make_decode_step(cfg: ArchConfig, kvcfg: PagedKVConfig,
                     hints=None, unroll: bool = False,
                     alloc_backend: Optional[str] = None,
                     alloc_policy: Optional[str] = None,
                     tenants=None, defer_refill: bool = False,
                     traced_classes: bool = False):
    """Returns serve_step(params, state) -> (state, logits, DecodeStats).

    The returned state carries no pool (``paged.k_pages``/``v_pages`` are
    None) and, for families with paged KV, the step's ``kv_write``: the
    caller puts the input's pool back, written by ``write_kv_in_place``.
    Returning the pool would make XLA copy it, since the step cannot write
    into an input it was not given.

    ``alloc_backend`` selects the support-core implementation for the
    decode burst (``jnp`` | ``kernel`` | ``kernel-interpret``; None resolves
    ``REPRO_ALLOC_BACKEND`` at trace time — see DESIGN.md §8);
    ``alloc_policy`` the central-allocator design (``freelist`` | ``bitmap``;
    None resolves ``REPRO_ALLOC_POLICY`` — DESIGN.md §9).

    ``tenants`` (a :class:`~repro.core.paged_kv.PagedTenants`) points the
    decode burst at this engine's namespaced tenant set on a shared
    multi-engine service; ``defer_refill=True`` (static) makes the step
    return a fourth :class:`~repro.core.paged_kv.PendingDecodeOps` value
    carrying the deferrable refill/flush traffic for the caller's burst
    window instead of committing it in-step (DESIGN.md §10).

    ``traced_classes=True`` (static) returns the TENANT-AGNOSTIC form
    ``serve_step(params, state, class_ids)`` (DESIGN.md §13): the shard's
    namespaced size-class ids arrive per call as a traced int32 vector
    (:meth:`~repro.core.paged_kv.PagedTenants.class_id_array` layout)
    instead of baking into the trace as Python constants, so N engine
    shards on one shared service can drive ONE jitted executable — the
    only things still static are the tenant-set STRUCTURE (which handles
    exist), the service's class count, and the backend/policy names.
    """
    window = recycle_window(cfg)

    def _serve_step(params: dict, state: ServeState, step_tenants):
        hidden, new_kv, new_rec = decode_hidden(
            params, cfg, kvcfg, state.paged, state.rec, state.tokens,
            enc_out=state.enc_out, hints=hints, unroll=unroll)
        with jax.named_scope("logits"):
            logits = decode_logits(params, cfg, hidden)
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        pending = kv_write = None
        if new_kv is not None:
            new_k, new_v = new_kv
            out = decode_alloc(
                kvcfg, state.paged,
                new_k.astype(kvcfg.dtype), new_v.astype(kvcfg.dtype),
                window=window, backend=alloc_backend, policy=alloc_policy,
                tenants=step_tenants, defer_refill=defer_refill)
            if defer_refill:
                paged, stats, kv_write, pending = out
            else:
                paged, stats, kv_write = out
        else:
            # attention-free (rwkv6): no pages; still advance lane clocks
            paged = state.paged._replace(
                seq_lens=state.paged.seq_lens + state.paged.active.astype(jnp.int32))
            stats = empty_decode_stats(kvcfg, tenants=step_tenants)
            if defer_refill:
                L = kvcfg.max_lanes
                pending = PendingDecodeOps(
                    below=jnp.zeros((L,), bool),
                    flush_mask=jnp.zeros((L,), bool),
                    flush_blocks=jnp.full((L,), -1, jnp.int32))

        new_state = ServeState(
            paged=paged._replace(k_pages=None, v_pages=None), rec=new_rec,
            tokens=next_tokens, enc_out=state.enc_out, step=state.step + 1,
            kv_write=kv_write)
        if defer_refill:
            return new_state, logits, stats, pending
        return new_state, logits, stats

    if traced_classes:
        if tenants is None:
            raise ValueError(
                "traced_classes=True needs a tenants handle set (its "
                "structure is static; only the class INDICES are traced)")

        def serve_step(params: dict, state: ServeState, class_ids):
            with use_hints(hints):
                return _serve_step(params, state,
                                   tenants.with_class_ids(class_ids))

        return serve_step

    def serve_step(params: dict, state: ServeState):
        with use_hints(hints):
            return _serve_step(params, state, tenants)

    return serve_step


class CountingJit:
    """``jax.jit`` wrapper that counts executable builds (trace events).

    The compile-telemetry primitive behind ``decode_compiles`` (DESIGN.md
    §13): a Python side-effect inside the wrapped function fires exactly
    when jax (re)traces — i.e. when a new executable is built — so
    ``compiles`` counts real compilations portably, without reaching into
    jit-cache internals.  ``compile_us`` accumulates the wall time of those
    tracing calls (trace + lowering + compile + the first execution —
    the full cold-start cost a shard pays before its first token).

    One shared instance across N engine shards is the shared-executable
    proof: if every shard's call signature matches (which traced class ids
    make true), ``compiles`` stays 1 however many shards step through it.
    """

    def __init__(self, fn):
        self.compiles = 0
        self.compile_us = 0.0
        self._tracing = False

        def _wrapped(*args):
            self._tracing = True
            return fn(*args)

        self._jit = jax.jit(_wrapped)

    def __call__(self, *args):
        self._tracing = False
        t0 = time.perf_counter()
        out = self._jit(*args)
        if self._tracing:
            self.compiles += 1
            self.compile_us += (time.perf_counter() - t0) * 1e6
        return out


class PrefillResult(NamedTuple):
    """Output of the family-dispatch prefill layer (engine admission unit).

    ``last_logits``  [B, V] logits at each sequence's last real position.
    ``kv``           (k, v) each [B, L_kv, T_kv, kv_heads, head_dim] —
                     batch-major, ready for ``paged_kv.admit_prefill_many``
                     (None for attention-free families).
    ``states``       per-layer recurrent states, family-specific layout
                     (None for pure-attention families).
    ``enc_out``      [B, F, d] whisper encoder output (None otherwise).
    """

    last_logits: jnp.ndarray
    kv: Optional[tuple]
    states: Optional[Any]
    enc_out: Optional[jnp.ndarray] = None


def make_family_prefill(cfg: ArchConfig, hints=None, unroll: bool = False,
                        recurrent_logits: bool = True):
    """The ONE prefill for all families (engine admission + prefill step).

    Returns ``prefill(params, batch) -> PrefillResult`` where ``batch`` holds
    ``tokens`` [B, T] (right-padded), ``lengths`` [B] real prompt lengths, and
    optionally ``frames`` / ``patches``.  Right-padding is invisible to the
    real positions for attention families (causal masking), so sequences of
    different lengths batch into one padded bucket — one XLA compile per
    bucket instead of one per prompt length.  Recurrent families (ssm,
    hybrid) fold padding into their state, so their buckets must be
    exact-length (see ``repro.serve.scheduler.pick_bucket``).

    ``recurrent_logits=False`` skips the vocab projection for ssm/hybrid
    (whose admission path seeds decode from the last prompt token and never
    reads logits) — at real scale that projection is a [B, d] x [d, ~100k]
    matmul the pre-refactor admission never paid.
    """
    from ..models import decode as dec
    from ..models.layers import embed
    from ..models.transformer import (_hybrid_stack, _rwkv_stack,
                                      _whisper_encoder, forward)

    def prefill(params: dict, batch: dict) -> PrefillResult:
      with use_hints(hints):
        toks = batch["tokens"]
        lengths = batch["lengths"].astype(jnp.int32)

        def _recurrent_last(h):
            if not recurrent_logits:
                return None
            h_last = jnp.take_along_axis(
                h, (lengths - 1)[:, None, None], axis=1)[:, 0]
            return dec.decode_logits(params, cfg, h_last)

        if cfg.family == "ssm":
            x = embed(params["embed"], toks)
            h, (wkv, tmp, cmp) = _rwkv_stack(params, cfg, x, remat=False,
                                             return_states=True, hints=hints,
                                             unroll=unroll)
            states = dec.RecurrentState(ssm=wkv, tm_prev=tmp, cm_prev=cmp)
            return PrefillResult(_recurrent_last(h), None, states)

        if cfg.family == "hybrid":
            x = embed(params["embed"], toks)
            h, ((ks, vs), (ssm, conv)) = _hybrid_stack(
                params, cfg, x, remat=False, return_kv=True,
                return_states=True, hints=hints, unroll=unroll)
            last = _recurrent_last(h)
            every = max(cfg.attn_every, 1)
            idx = np.arange(every - 1, cfg.num_layers, every)
            kv = (ks[idx].swapaxes(0, 1), vs[idx].swapaxes(0, 1))
            return PrefillResult(last, kv, dec.RecurrentState(ssm=ssm, conv=conv))

        # --- attention families (dense / moe / vlm / audio) ---
        enc_out = None
        last_idx = lengths - 1
        if cfg.family == "audio":
            enc_out = _whisper_encoder(params, cfg, batch["frames"],
                                       unroll=unroll)
            logits, kv = forward(params, cfg, toks,
                                 encoder_frames=batch["frames"],
                                 return_kv=True, hints=hints, unroll=unroll)
        elif cfg.family == "vlm" and batch.get("patches") is not None:
            logits, kv = forward(params, cfg, toks,
                                 prefix_embeds=batch["patches"],
                                 return_kv=True, hints=hints, unroll=unroll)
            last_idx = last_idx + batch["patches"].shape[1]
        elif batch.get("prefix_k") is not None:
            # Prefix-cache hit (DESIGN.md §11): ``tokens`` is the uncached
            # SUFFIX and prefix_k/v [B, L, P, kv, hd] the cached pages'
            # K/V for absolute positions [0, P).  The forward attends over
            # the concatenation; logits and KV come back suffix-only, and
            # ``lengths`` count suffix tokens.
            pk, pv = batch["prefix_k"], batch["prefix_v"]
            logits, kv = forward(
                params, cfg, toks, return_kv=True, hints=hints,
                unroll=unroll,
                prefix_kv=(pk.swapaxes(0, 1), pv.swapaxes(0, 1)),
                pos_offset=pk.shape[2])
        else:
            logits, kv = forward(params, cfg, toks, return_kv=True,
                                 hints=hints, unroll=unroll)
        last = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]
        ks, vs = kv                                  # [L, B, S, kv, hd]
        return PrefillResult(last, (ks.swapaxes(0, 1), vs.swapaxes(0, 1)),
                             None, enc_out=enc_out)

    return prefill


def make_prefill_step(cfg: ArchConfig, hints=None, unroll: bool = False):
    """Full-sequence forward returning last-position logits + stacked KV.

    Thin wrapper over :func:`make_family_prefill` keeping the historical
    contract (``(logits [B, 1, V], kv [L, B, S, kv, hd] | None)``) for the
    dry-run/lowering path.  Batches without ``lengths`` are treated as
    full-length (no padding).
    """
    fam = make_family_prefill(cfg, hints=hints, unroll=unroll)

    def prefill_step(params: dict, batch: dict):
        B, T = batch["tokens"].shape
        if "lengths" not in batch:
            batch = dict(batch, lengths=jnp.full((B,), T, jnp.int32))
        # Serving admission needs only the LAST position's logits (the full
        # [B, S, V] tensor is a train-path artifact; returning it would cost
        # up to 100+ GB/device at the 32k prefill shapes).
        res = fam(params, batch)
        kv = None
        if res.kv is not None and cfg.family != "hybrid":
            ks, vs = res.kv
            kv = (ks.swapaxes(0, 1), vs.swapaxes(0, 1))
        return res.last_logits[:, None], kv

    return prefill_step
