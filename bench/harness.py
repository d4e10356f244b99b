"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is the whole run; ``bench/run.py`` is its command line.
Tests call it with ``require_tpu=False`` and smoke-size overrides.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time

from . import correct, spec, traffic
from .driver import Driver
from .spec import BENCH_DIR, ROOT

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the persistent compile cache, inside the checkout at a fixed path
CACHE_DIR = ROOT / ".jax_cache"
#: requests still serving this long after the window closes are failed
GRACE_S = 60.0


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class RunData:
    """What the metric readers read: the whole interface a reader under
    ``bench/metrics/`` may use, so that a new metric is a new file."""
    model: dict
    window: object                 # driver.WindowResult
    setup_s: float
    stats0: dict
    stats1: dict
    lanes: int
    num_pages: int
    prefix_cache: bool
    kv_peak_used: int | None
    trace: object | None           # trace.Reduced
    peak: dict


def read_metric(name: str, run: RunData):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


class _CompileCounter:
    """Counts XLA compiles and persistent-cache hits while ``on``.  One per
    process: JAX has no way to take a listener off again."""

    _one = None

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.compiles = cls._one.cache_hits = 0
        cls._one.names = {}
        return cls._one

    def __init__(self):
        import jax
        self.on = False
        self.compiles = 0
        self.cache_hits = 0
        self.names: dict[str, int] = {}

        def dur(event, duration, fun_name="", **kw):
            if self.on and event == COMPILE_EVENT:
                self.compiles += 1
                self.names[fun_name] = self.names.get(fun_name, 0) + 1

        def ev(event, **kw):
            if self.on and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(dur)
        jax.monitoring.register_event_listener(ev)


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX reports "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def _check_tree(params, cfg, dtype) -> None:
    import jax
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(cfg, 0, dtype))
    got_s = jax.tree_util.tree_structure(params)
    if got_s != jax.tree_util.tree_structure(want) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(params), jax.tree.leaves(want))):
        raise ValueError("bench weight layout differs from the program's "
                         "parameter tree")


def _snapshot(eng) -> dict:
    return {k: v for k, v in dataclasses.asdict(eng.stats).items()
            if isinstance(v, (int, float))}


def enable_cache() -> None:
    """The program's persistent compile cache, at the fixed directory
    ``.jax_cache`` of this checkout whatever the environment names.

    It keeps every program: the admission path re-traces small ``scan``
    and ``cond`` programs on every call, and below JAX's default one-second
    threshold each would compile anew in every run.  It is not capped: a
    cap makes JAX rescan the whole directory on every write, which with
    the thousands of small programs that set-up compiles costs more than
    compiling them, and evicts programs that the next run needs."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def build(cell, model: dict, seed: int, seconds: float,
          mix_spec: dict | None = None):
    """The engine, scheduler and requests of one run, warmed up."""
    import jax
    import jax.numpy as jnp
    from repro.models import make_paged_config
    from repro.serve.engine import ServingEngine
    from repro.serve.scheduler import Scheduler, make_scheduler_config
    from .warmup import warm_up
    from .weights import make_weights

    cfg = spec.family(model).arch_config(model)
    srv = model["serving"]
    dtype = jnp.dtype(srv["dtype"])
    kvcfg = make_paged_config(cfg, seq_len=srv["seq_len"],
                              lanes=srv["lanes"], page_size=srv["page_size"],
                              dtype=dtype)
    mix = traffic.build(cell.traffic, cfg.vocab_size, seed, seconds,
                        spec=mix_spec)
    scfg = make_scheduler_config(
        cfg, kvcfg, max_prompt_len=traffic.max_prompt_len(mix.spec))
    params = make_weights(model, seed, dtype)
    _check_tree(params, cfg, dtype)
    eng = ServingEngine(cfg, kvcfg, params, dtype=dtype, sched_cfg=scfg,
                        prefix_cache=mix.prefix_cache)
    sched = Scheduler(scfg)
    n_pass = warm_up(eng, sched, mix, cfg.vocab_size, seed)
    jax.block_until_ready(eng.state)
    return eng, sched, mix, n_pass


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             compile_cache: bool = True, model: dict | None = None,
             mix_spec: dict | None = None, hook=None,
             control: str | None = None, limit: float | None = None,
             log=sys.stderr) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``control`` (``"fp8"``) puts the control in the program's place: on
    the same sample, ``logit_gap`` is the reference's gap at the token
    that the lower-precision reference puts first, judged against the
    same limit, so ``correct`` is the control's.  The program's own
    reading is kept under the extra key ``control``.  ``limit`` replaces
    the cell's ``logit_gap`` limit (smoke sizes in tests).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    if compile_cache:
        enable_cache()
    cell = spec.load_cell(name)
    devs = check_devices(cell.chips, require_tpu)
    model = model if model is not None else cell.model
    eng, sched, mix, n_pass = build(cell, model, seed, seconds, mix_spec)
    kvcfg, srv = eng.kvcfg, model["serving"]
    from .peaks import peak
    counter = _CompileCounter.get()
    setup_s = time.perf_counter() - t_start
    print(f"{name}: seed {seed}, backend {eng.alloc_backend}, prefix "
          f"{'cache ' + eng.prefix_alias if eng.cache else 'cache off'}, "
          f"{kvcfg.num_pages} pages x {kvcfg.page_size}, "
          f"{srv['lanes']} lanes, {len(mix.items)} requests in the mix, "
          f"{n_pass} warm-up passes, set-up {setup_s:.3f} s", file=log,
          flush=True)

    stats0 = _snapshot(eng)
    driver = Driver(eng, sched, annotate=trace)
    if hook is not None:       # tests only: sees (or breaks) the engine
        hook(eng)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        driver.track_pool(eng.tenants.kv.size_class)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only, no call tracing
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.on = True
    with (jax.profiler.TraceAnnotation("bench.window") if trace
          else contextlib.nullcontext()):
        if mix.loop == "open":
            win = driver.open_loop(mix.items, seconds, GRACE_S)
        else:
            win = driver.closed_loop(mix.items, mix.clients, seconds,
                                     GRACE_S)
    counter.on = False
    reduced = None
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        from . import trace as tr
        t1 = time.perf_counter()
        loaded = tr.load(tr.find_xplane(trace_dir))
        t2 = time.perf_counter()
        reduced = tr.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: stop {t1 - t0:.3f} s, load {t2 - t1:.3f} s, reduce "
              f"{time.perf_counter() - t2:.3f} s", file=log, flush=True)
    print(f"window: {len(win.records)} requests, {len(win.steps)} decode "
          f"steps, drained at {win.end:.3f} s; compiles in the window "
          f"{counter.compiles}, of which {counter.cache_hits} came from the "
          f"persistent cache: "
          f"{sorted(counter.names.items(), key=lambda kv: -kv[1])[:8]}",
          file=log, flush=True)

    checks = {"unfinished": correct.unfinished(win.records)}
    checks.update(correct.allocator_checks(eng))
    run = RunData(model=model, window=win, setup_s=setup_s, stats0=stats0,
                  stats1=_snapshot(eng), lanes=srv["lanes"],
                  num_pages=kvcfg.num_pages, prefix_cache=mix.prefix_cache,
                  kv_peak_used=driver.kv_peak,
                  trace=reduced,
                  peak=peak(devs[0].device_kind) if require_tpu
                  else {"bf16_flops": math.inf})
    mem = [d.memory_stats() or {} for d in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(int(m.get("peak_bytes_in_use", 0))
                                       for m in mem)}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    del eng, sched, driver
    gc.collect()

    picked = correct.sample(win.records, seed)
    pad_to = -(-traffic.max_seq_len(mix.spec) // 128) * 128
    t0 = time.perf_counter()
    gaps, ctrl = correct.logit_gaps(model, seed, picked, pad_to, control)
    t_ref = time.perf_counter() - t0
    widest = lambda gs: max((float(g.max()) for g in gs), default=math.inf)
    checks["logit_gap"] = widest(gaps if ctrl is None else ctrl)
    served = sum(len(g) for g in gaps)
    limits = {"unfinished": 0, "invariants": 0, "leaked_pages": 0,
              "logit_gap": cell.limits["logit_gap"]["limit"]
              if limit is None else limit}
    ok = all(checks[k] <= limits[k] for k in limits)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(ok), "attempted": len(win.records),
           "failed": checks["unfinished"], "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {
            "device_ops": [[f"module {k}", v] for k, v in sorted(
                reduced.module_s.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in reduced.idle_by_span]}
        print("top device ops: " + json.dumps(reduced.top_ops), file=log)
    if ctrl is not None:
        out["control"] = {"quant": control,
                          "program_logit_gap": widest(gaps)}
        print(f"control ({control}) in the program's place; the "
              f"program's own logit_gap: {widest(gaps)}", file=log)
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in limits}
    print(f"compared {len(picked)} requests, {served} served tokens, with "
          f"the float32 reference in {t_ref:.3f} s", file=log)
    for k in limits:
        print(f"check {k}: {checks[k]} (limit {limits[k]})", file=log)
    log.flush()
    return out
