"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Scheduler-driven continuous-batching demo on the SpeedMalloc paged KV cache:
Poisson-ish request arrivals with Pareto-ish lengths (the paper's
Larson-style server-client pattern) flow through the request-lifecycle
scheduler (DESIGN.md §3) — waiting queue -> prefill buckets -> running lanes
-> completion.  Each admission batch costs ONE support-core HMQ burst and at
most one XLA compile per prefill bucket; decode issues one HMQ batch per
step; completion releases lanes through OP_FREE/FREE_ALL packets.  Prints
allocator + scheduler telemetry (live pages, peak, bursts, compiles).

``--engines N`` (N > 1) switches to the multi-engine sharded deployment
(DESIGN.md §10): N engine shards registered as disjoint namespaced tenant
sets on ONE shared AllocService, an async decode loop that merges every
shard's deferrable allocator traffic into one commit per ``--quantum``-step
burst window, and (with ``--preemption``) scheduler eviction of
lowest-priority lanes under pool pressure.

``--loadgen poisson|bursty|diurnal`` replaces the closed-loop drain with
the OPEN-loop driver (DESIGN.md §14): a seeded arrival process with
heavy-tailed lengths submits requests by virtual arrival time regardless
of completion, and the run reports p50/p90/p99 time-to-first-token,
per-token latency, and queue depth instead of just throughput.
``--record-trace FILE`` additionally serializes the allocator-op stream to
a versioned tracefile for model-free replay (``repro.loadgen.trace``).
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from ..alloc import ALLOC_POLICIES, EVICTION_POLICIES
from ..configs.base import ARCH_IDS, smoke_config
from ..core.paged_kv import live_pages
from ..core.support_core import ALLOC_BACKENDS
from ..models import init_params, make_paged_config
from ..serve.engine import ServingEngine, run_admission
from ..serve.multi_engine import MultiEngine
from ..serve.router import ROUTER_POLICIES
from ..serve.scheduler import Request, Scheduler, make_scheduler_config
from .compile_cache import enable_compile_cache


def synth_requests(cfg, n: int, rng: np.random.RandomState,
                   priority_every: int = 0) -> list[Request]:
    """Larson-style synthetic request mix.  ``priority_every=k`` marks every
    k-th request priority 1 (the preemption demo's high-priority tier)."""
    reqs = []
    for rid in range(n):
        plen = int(rng.pareto(2.0) * 20) % 96 + 8
        reqs.append(Request(
            rid=rid,
            tokens=rng.randint(0, cfg.vocab_size, size=plen).astype(np.int32),
            frames=(rng.randn(cfg.encoder_seq_len, cfg.d_model).astype(np.float32)
                    if cfg.family == "audio" else None),
            patches=(rng.randn(4, cfg.d_model).astype(np.float32)
                     if cfg.family == "vlm" else None),
            priority=1 if priority_every and rid and rid % priority_every == 0
            else 0,
        ))
    return reqs


def serve_loop(eng: ServingEngine, sched: Scheduler,
               requests: list[Request], max_new_tokens: int,
               log_every: int = 8, verbose: bool = True,
               preemption: bool = False) -> int:
    """Drive the scheduler/engine lifecycle until every request completes.

    Returns the number of decode steps taken.  If admission starves with
    nothing running — the pool cannot fit any waiting request — the loop
    stops and reports the stranded requests loudly rather than silently
    undercounting.  ``preemption`` enables the
    scheduler's priority eviction (DESIGN.md §10): when a waiting request
    outranks a running one and admission is stuck, the lowest-priority
    running lane is FREE_ALLed and its request re-queued with its generated
    prefix.
    """
    for req in requests:
        req.max_new_tokens = max_new_tokens
        sched.submit(req)

    step = 0
    while sched.has_work:
        progressed = run_admission(eng, sched, preemption=preemption)
        if not sched.running:
            if progressed:
                continue     # whole batch retired at the admission seed
                             # (max_new_tokens == 1): admit the next one
            break                      # nothing admissible: pool too small
        tokens = eng.step()
        step += 1
        finished = sched.note_decode_step(tokens)
        if finished:
            # demotion keys must be captured before sched.complete drops
            # the running entries (prefix cache on only)
            kv_toks = {l: sched.kv_token_prefix(l) for l in finished} \
                if eng.cache is not None else None
            eng.release(finished, kv_tokens=kv_toks)
            sched.complete(finished)
        if verbose and step % log_every == 0:
            print(f"step {step}: done={len(sched.finished)}/{len(requests)} "
                  f"waiting={len(sched.waiting)} "
                  f"live_pages={eng.live_pages} "
                  f"peak={int(eng.state.paged.alloc.peak_used[eng.tenants.kv.size_class])}")
    if sched.waiting:
        print(f"WARNING: admission starved — {len(sched.waiting)} request(s) "
              f"not served (page budget {eng.free_pages} free - "
              f"{sched.scfg.page_reserve} reserve cannot fit the next one)")
    return step


def serve_loadgen(cfg, kvcfg, params, scfg, args) -> None:
    """Open-loop path of the launcher (DESIGN.md §14): seeded arrivals,
    virtual-time submission, tail-latency report, optional trace record."""
    from ..loadgen import LoadgenSpec, build_workload, run_open_loop
    from ..loadgen.trace import record_service, save_trace

    me = MultiEngine(cfg, kvcfg, params, n_engines=args.engines,
                     dtype=jnp.float32, sched_cfg=scfg,
                     quantum=args.quantum, preemption=args.preemption,
                     router=args.router, alloc_backend=args.alloc_backend,
                     alloc_policy=args.alloc_policy,
                     prefix_cache=args.prefix_cache == "on",
                     eviction=args.eviction,
                     cache_pages=args.cache_pages,
                     prefix_alias=args.prefix_alias)
    rec = record_service(me.service) if args.record_trace else None
    spec = LoadgenSpec(n_requests=args.requests, arrival=args.loadgen,
                       rate=args.rate, priority_frac=args.priority_frac,
                       shared_prefix_frac=args.shared_prefix_frac,
                       output_cap=args.max_new_tokens, seed=args.seed)
    timed = build_workload(spec, cfg.vocab_size)
    report = run_open_loop(me, timed, max_windows=args.max_windows,
                           verbose=True)
    print(f"open-loop {spec.arrival} rate={spec.rate}/step seed={spec.seed}: "
          f"completed={report.completed} failed={report.failed} "
          f"stranded={report.stranded} in {report.windows} windows "
          f"({report.wall_s:.1f}s)")
    print(f"  TTFT p50={report.p50_ttft_us / 1e3:.1f}ms "
          f"p90={report.p90_ttft_us / 1e3:.1f}ms "
          f"p99={report.p99_ttft_us / 1e3:.1f}ms "
          f"(virtual: p50={report.p50_ttft_steps:.1f} "
          f"p99={report.p99_ttft_steps:.1f} steps)")
    print(f"  per-token p50={report.p50_tpot_us / 1e3:.1f}ms "
          f"p99={report.p99_tpot_us / 1e3:.1f}ms | "
          f"queue depth mean={report.queue_depth_mean:.1f} "
          f"max={report.queue_depth_max}")
    for i, e in enumerate(me.engines):
        kv_frag = next((rep for name, rep in e.fragmentation_report().items()
                        if name.endswith("kv_pages")), None)
        if kv_frag is None:
            continue
        print(f"  e{i}: mean_run_len={e.stats.mean_run_len:.2f} "
              f"external_frag={kv_frag['external_frag']:.2f} "
              f"largest_free_run={kv_frag['largest_free_run']} "
              f"splits={kv_frag['split_count']} merges={kv_frag['merge_count']}")
    if rec is not None:
        me.service.recorder = None
        trace = rec.finish(
            complete=sum(e.stats.decode_bursts for e in me.engines) == 0)
        save_trace(trace, args.record_trace)
        print(f"  trace: {trace.bursts} bursts ({trace.live_bursts} live, "
              f"{trace.ops} ops) {trace.windows} windows -> "
              f"{args.record_trace} complete={trace.header['complete']} "
              f"(replay: python -m repro.launch.replay {args.record_trace})")


def serve_multi(cfg, kvcfg, params, scfg, requests, args) -> None:
    """Multi-engine sharded serving path of the launcher (DESIGN.md §10)."""
    me = MultiEngine(cfg, kvcfg, params, n_engines=args.engines,
                     dtype=jnp.float32, sched_cfg=scfg,
                     quantum=args.quantum, preemption=args.preemption,
                     router=args.router, alloc_backend=args.alloc_backend,
                     alloc_policy=args.alloc_policy,
                     prefix_cache=args.prefix_cache == "on",
                     eviction=args.eviction,
                     cache_pages=args.cache_pages,
                     prefix_alias=args.prefix_alias)
    windows = me.serve(requests, max_new_tokens=args.max_new_tokens,
                       verbose=True)
    st = me.stats
    failed = me.failed
    if failed:
        print(f"FAILED: {len(failed)} request(s) rejected by the allocator")
    print(f"served {len(me.finished)} requests across {args.engines} engines "
          f"in {windows} windows ({st.decode_steps} engine-steps) | "
          f"alloc_backend={me.alloc_backend} alloc_policy={me.alloc_policy} "
          f"router={args.router} quantum={args.quantum} "
          f"preemption={args.preemption} | "
          f"window_commits={st.window_commits} "
          f"cross_engine_burst_occupancy={st.cross_engine_burst_occupancy:.2f} "
          f"preemptions={st.preemptions} | "
          # one tenant-agnostic decode executable for all shards (§13):
          # decode_compiles stays 1 however many engines are deployed
          f"decode_compiles={st.decode_compiles} "
          f"decode_compile_ms={st.decode_compile_us / 1e3:.0f}")
    for i, eng in enumerate(me.engines):
        s = eng.stats
        cache = (f" cache_hit_rate={s.cache_hit_rate:.2f} "
                 f"prefill_tokens_saved={s.prefill_tokens_saved} "
                 f"aliased_pages={s.aliased_pages} "
                 f"hit_copy_bytes={s.cache_hit_copy_bytes}"
                 if eng.cache is not None else "")
        print(f"  e{i}: admitted={s.admitted} completed={s.completed} "
              f"decode_steps={s.decode_steps} "
              f"stash_hit_rate={s.stash_hit_rate:.2f} "
              f"decode_bursts/1k={s.hmq_bursts_per_1k_decode_steps:.0f}"
              f"{cache}")
    print("cross-engine tenant rollup (one shared AllocService):")
    for name, d in me.tenant_rollup().items():
        print(f"  {name}: engines={d['engines']} used={d['used']}/{d['quota']} "
              f"peak={d['peak_used']} allocs={d['alloc_count']} "
              f"frees={d['free_count']} fails={d['fail_count']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--engines", type=int, default=1,
                    help="engine shards on ONE shared AllocService; >1 "
                         "drives the multi-engine async loop (DESIGN.md §10)")
    ap.add_argument("--quantum", type=int, default=4,
                    help="burst-window length in decode steps (multi-engine "
                         "loop): deferred allocator traffic from every shard "
                         "merges into one commit per window")
    ap.add_argument("--preemption", action="store_true",
                    help="evict the lowest-priority running lane when a "
                         "higher-priority request cannot be admitted")
    ap.add_argument("--router", default="round_robin",
                    choices=list(ROUTER_POLICIES),
                    help="multi-engine request routing policy")
    ap.add_argument("--priority-every", type=int, default=0,
                    help="mark every k-th synthetic request priority 1 "
                         "(exercises --preemption)")
    ap.add_argument("--stash-size", type=int, default=None,
                    help="per-lane page-stash size (0 disables the front "
                         "tier; default: autotuned from boundary cadence)")
    ap.add_argument("--alloc-backend", default=None,
                    choices=list(ALLOC_BACKENDS),
                    help="support-core step implementation (default: "
                         "REPRO_ALLOC_BACKEND env or 'jnp'; 'kernel' is the "
                         "fused Pallas burst, TPU only; 'kernel-interpret' "
                         "runs it through the Pallas interpreter)")
    ap.add_argument("--alloc-policy", default=None,
                    choices=list(ALLOC_POLICIES),
                    help="central-allocator policy (default: "
                         "REPRO_ALLOC_POLICY env or 'freelist'; 'bitmap' is "
                         "the address-ordered first-fit AllocatorPolicy — "
                         "DESIGN.md §9)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="keep completed requests' full KV pages cached by "
                         "token prefix and skip their prefill on a hit "
                         "(DESIGN.md §11)")
    ap.add_argument("--eviction", default=None,
                    choices=list(EVICTION_POLICIES),
                    help="prefix-cache eviction policy (default: "
                         "REPRO_KV_EVICTION env or 'lru')")
    ap.add_argument("--cache-pages", type=int, default=None,
                    help="prefix-cache page budget (default: half the KV "
                         "pool; charged against the kv tenant quota)")
    ap.add_argument("--prefix-alias", default=None, choices=["copy", "alias"],
                    help="prefix-cache hit admission mode (default: "
                         "REPRO_PREFIX_ALIAS env or 'copy'): 'copy' gathers "
                         "cached K/V into fresh lane pages, 'alias' splices "
                         "the cache pages into the lane's block table with a "
                         "refcount bump — zero copy (DESIGN.md §12)")
    ap.add_argument("--loadgen", default="off",
                    choices=["off", "poisson", "bursty", "diurnal"],
                    help="open-loop arrival process (DESIGN.md §14); "
                         "anything but 'off' drives the multi-engine loop "
                         "by virtual arrival time and reports TTFT "
                         "percentiles instead of closed-loop throughput")
    ap.add_argument("--rate", type=float, default=0.15,
                    help="open-loop mean arrivals per decode step")
    ap.add_argument("--priority-frac", type=float, default=0.0,
                    help="open-loop fraction of requests at priority 1")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="open-loop fraction of prompts opening with one "
                         "common prefix (exercises --prefix-cache)")
    ap.add_argument("--record-trace", default=None, metavar="FILE",
                    help="serialize the allocator-op stream of the "
                         "open-loop run to FILE for model-free replay")
    ap.add_argument("--max-windows", type=int, default=None,
                    help="open-loop window budget (smoke-run bound)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = smoke_config(args.arch)
    rng = np.random.RandomState(args.seed)
    kvcfg = make_paged_config(cfg, seq_len=256, lanes=args.lanes,
                              page_size=args.page_size, dtype=jnp.float32,
                              stash_size=args.stash_size)
    params = init_params(cfg, dtype=jnp.float32)
    scfg = make_scheduler_config(cfg, kvcfg, max_prompt_len=128)
    if args.loadgen != "off":
        serve_loadgen(cfg, kvcfg, params, scfg, args)
        return

    requests = synth_requests(cfg, args.requests, rng,
                              priority_every=args.priority_every)

    if args.engines > 1:
        serve_multi(cfg, kvcfg, params, scfg, requests, args)
        return

    eng = ServingEngine(cfg, kvcfg, params, dtype=jnp.float32, sched_cfg=scfg,
                        alloc_backend=args.alloc_backend,
                        alloc_policy=args.alloc_policy,
                        prefix_cache=args.prefix_cache == "on",
                        eviction=args.eviction,
                        cache_pages=args.cache_pages,
                        prefix_alias=args.prefix_alias)
    sched = Scheduler(scfg)

    steps = serve_loop(eng, sched, requests, args.max_new_tokens,
                       preemption=args.preemption)

    a = eng.state.paged.alloc
    s = eng.stats
    kv_cls = eng.tenants.kv.size_class
    if sched.failed:
        print(f"FAILED: {len(sched.failed)} request(s) rejected by the allocator")
    print(f"served {len(sched.finished)} requests in {steps} decode steps | "
          f"alloc_backend={eng.alloc_backend} alloc_policy={eng.alloc_policy} "
          f"stash={kvcfg.stash_size}/{kvcfg.stash_watermark}"
          f"/{kvcfg.stash_refill} | "
          f"allocs={int(a.alloc_count[kv_cls])} frees={int(a.free_count[kv_cls])} "
          f"fails={int(a.fail_count[kv_cls])} peak_pages={int(a.peak_used[kv_cls])} "
          f"live={int(live_pages(eng.state.paged, eng.tenants))} | "
          f"admit_bursts={s.hmq_admit_bursts} "
          f"({s.hmq_admit_bursts / max(s.admitted, 1):.2f}/seq) "
          f"prefill_compiles={s.prefill_compiles} "
          f"decode_compiles={s.decode_compiles} "
          f"decode_compile_ms={s.decode_compile_us / 1e3:.0f} "
          f"preemptions={s.preemptions} | "
          f"stash_hit_rate={s.stash_hit_rate:.2f} "
          f"decode_bursts/1k={s.hmq_bursts_per_1k_decode_steps:.0f} "
          f"stash_depth_hist={s.stash_depth_hist}")
    if eng.cache is not None:
        print(f"prefix_cache: hit_rate={s.cache_hit_rate:.2f} "
              f"prefill_tokens_saved={s.prefill_tokens_saved} "
              f"pages={s.cache_pages}/{eng.cache.budget} "
              f"inserts={s.cache_inserts} evictions={s.cache_evictions} "
              f"policy={eng.cache.policy.name} mode={eng.prefix_alias} "
              f"aliased_pages={s.aliased_pages} "
              f"hit_copy_bytes={s.cache_hit_copy_bytes} "
              f"hit_admit_us={s.hit_admit_us:.0f}")
    # contiguity + fragmentation: what the policy's placement actually did
    # to the address space (DESIGN.md §15)
    frag = eng.fragmentation_report()
    kv_frag = next((rep for name, rep in frag.items()
                    if name.endswith("kv_pages")), None)
    if kv_frag is not None:
        print(f"contiguity: mean_run_len={s.mean_run_len:.2f} "
              f"extents={s.contiguous_extents} "
              f"external_frag={kv_frag['external_frag']:.2f} "
              f"largest_free_run={kv_frag['largest_free_run']} "
              f"splits={kv_frag['split_count']} "
              f"merges={kv_frag['merge_count']} "
              f"compactions={s.compactions} "
              f"compaction_moves={s.compaction_moves}")
    # per-tenant view: the multi-tenant support-core claim, measured
    print(f"burst_occupancy={s.burst_occupancy:.2f} | tenants:")
    for name, rep in eng.tenant_report().items():
        acc = s.tenants.get(name, {})
        print(f"  {name}: used={rep['used']}/{rep['quota']} "
              f"peak={rep['peak_used']} allocs={rep['alloc_count']} "
              f"frees={rep['free_count']} fails={rep['fail_count']} "
              f"(burst mallocs={acc.get('mallocs', 0)} "
              f"failed={acc.get('failed', 0)})")


if __name__ == "__main__":
    main()
