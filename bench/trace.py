"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps
labelled by host spans, and per-module device time.

``load`` reads the file into plain interval lists; ``reduce`` works on
those lists alone, so a test can hand it a synthetic trace.

Names this depends on (written down in PERF.md):

* device planes are named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per device operation and ``XLA Modules``
  one per executable run;
* host spans are the driver's ``bench.*`` annotations (``driver.SPANS``
  inside ``bench.window``).
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    #: per device plane: (name, start_ns, end_ns) of each operation
    ops: dict[str, list[tuple[str, float, float]]]
    #: per device plane: (name, start_ns, end_ns) of each executable run
    modules: dict[str, list[tuple[str, float, float]]]
    #: host annotations: (name, start_ns, end_ns)
    spans: list[tuple[str, float, float]]


def find_xplane(log_dir: str | Path) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str | Path, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: dict = {}
    modules: dict = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dst = modules.setdefault(plane.name, [])
                else:
                    continue
                dst.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return Trace(ops=ops, modules=modules, spans=spans)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window(trace: Trace) -> tuple[float, float]:
    w = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not w:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return w[0]


def _labeller(spans):
    """A function that names the span covering most of a gap (the first
    in ``spans`` on a tie), or ``"no span"``.  Spans are sorted by start
    once, so each gap looks only at the spans that can reach it."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    starts = [spans[i][1] for i in order]
    reach = list(itertools.accumulate((spans[i][2] for i in order), max))

    def label(gap: tuple[float, float]) -> str:
        best, first, name = 0.0, len(spans), "no span"
        k = bisect.bisect_left(starts, gap[1]) - 1
        while k >= 0 and reach[k] > gap[0]:
            i = order[k]
            n, s, e = spans[i]
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > best or (ov == best and ov > 0 and i < first):
                best, first, name = ov, i, n
            k -= 1
        return name

    return label


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # union of device operations, mean over chips
    idle_by_span: list            # [(span, seconds)] most first
    module_s: dict                # module name -> device seconds (all chips)
    module_runs: dict             # module name -> number of runs
    top_ops: list                 # [(op name, seconds)] most first


def reduce(trace: Trace, top: int = 10) -> Reduced:
    w0, w1 = window(trace)
    label = _labeller([(n, s, e) for n, s, e in trace.spans
                       if n != WINDOW_SPAN])
    busy_total = 0.0
    idle: dict[str, float] = {}
    op_s: dict[str, float] = {}
    for plane, evs in trace.ops.items():
        busy = merge(clip([(s, e) for _, s, e in evs], w0, w1))
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                lab = label((s, e))
                idle[lab] = idle.get(lab, 0.0) + (e - s) / 1e9
        for n, s, e in evs:
            if e > w0 and s < w1:
                n = n.split(" = ")[0]        # the op, not its HLO text
                op_s[n] = op_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
    n_dev = max(len(trace.ops), 1)
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    for evs in trace.modules.values():
        for n, s, e in evs:
            if e > w0 and s < w1:
                module_s[n] = module_s.get(n, 0.0) + (e - s) / 1e9
                module_runs[n] = module_runs.get(n, 0) + 1
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    idle = {k: v / n_dev for k, v in idle.items()}
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy_total / 1e9 / n_dev,
                   idle_by_span=rank(idle), module_s=module_s,
                   module_runs=module_runs, top_ops=rank(op_s))


def module_time(red: Reduced, prefix: str) -> tuple[float, int]:
    """Device seconds and runs of the modules whose name starts with
    ``prefix``."""
    s = sum(v for k, v in red.module_s.items() if k.startswith(prefix))
    n = sum(v for k, v in red.module_runs.items() if k.startswith(prefix))
    return s, n
