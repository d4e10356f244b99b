"""The program's own breakdown of a profiler trace: device time of the
decode step by named scope, the device's idle time between decode runs,
and idle gaps labelled by the program's ``repro.*`` spans.

It adds to ``bench.trace`` and changes nothing there: ``load`` reads the
same ``.xplane.pb`` for what ``bench.trace.load`` leaves out (the
``repro.*`` spans), ``op_paths_from_hlo`` maps the decode executable's
instruction names to their scope paths, and ``reduce`` works on the
``bench.trace.Trace`` and that, so a test can hand it synthetic lists.

Names this depends on (PERF.md section 3):

* the program's host spans start ``repro.`` (``src/repro/obs.py``) and
  nest inside the driver's ``bench.*`` spans on one thread;
* the decode step's named scopes are ``attention`` (``gather_kv`` inside
  it), ``mlp``, ``logits``, ``alloc`` and ``kv_write``.  An op's scope
  path is its HLO ``op_name`` metadata.  The TPU trace's op events carry
  no metadata, only the instruction's name, so the path comes from the
  compiled decode executable's HLO text;
* on a trace line a ``while`` op's event holds its body's op events, so
  device time is counted by self time: an op's duration less that of the
  ops nested in it.

Run as a tool, it makes one traced run of a cell through the harness and
prints this breakdown beside the run's result line::

    python3 -m bench.program_trace --workload <cell> --seed <n> \\
        --seconds <s> [--out breakdown.json]
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
import re
import statistics
from pathlib import Path

from . import trace as tr
from .stats import DECODE_MODULE

#: the decode step's disjoint top-level scopes, and the sub-scopes reported
SCOPES = ("attention", "mlp", "logits", "alloc", "kv_write")
SUB_SCOPES = ("gather_kv",)
UNSCOPED = "unscoped"
#: spans between two decode runs that make their gap not a decode gap
NOT_DECODE = ("repro.admission", "repro.release")


@dataclasses.dataclass
class ProgramTrace:
    #: host spans ``repro.*`` and ``bench.*``: (name, start_ns, end_ns)
    spans: list[tuple[str, float, float]]
    #: instruction name -> scope path (its ``op_name``), for the decode
    #: executable's instructions that carry one
    op_paths: dict[str, str]


def scope_of(path: str) -> tuple[str, str | None]:
    """(top-level scope, sub-scope or None) of an ``op_name`` path."""
    parts = path.split("/")
    top = next((p for p in parts if p in SCOPES), UNSCOPED)
    sub = next((p for p in parts if p in SUB_SCOPES), None)
    return top, sub


def op_name(event_name: str) -> str:
    """The HLO instruction of a trace op event: ``%fusion.3 = ...`` ->
    ``fusion.3``."""
    return event_name.split(" = ")[0].lstrip("%")


_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                     r'metadata=\{[^}]*op_name="([^"]*)"', re.M)


def op_paths_from_hlo(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` from a compiled module's HLO text."""
    return {m.group(1): m.group(2) for m in _HLO_OP.finditer(text)}


def load(path: str | Path, hlo_text: str = "") -> ProgramTrace:
    """The host spans of a trace file, and the scope paths of the decode
    executable whose compiled HLO text is ``hlo_text``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("repro.", "bench."))]
    return ProgramTrace(spans=spans, op_paths=op_paths_from_hlo(hlo_text))


def _innermost_segments(spans):
    """Flatten properly nested spans into disjoint (start, end, name)
    segments, each named by the innermost span open over it."""
    events = sorted(spans, key=lambda s: (s[1], -s[2]))
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float, float]] = []
    t = events[0][1] if events else 0.0

    def close_until(x):
        nonlocal t
        while stack and stack[-1][2] <= x:
            n, _, e = stack.pop()
            if e > t:
                segs.append((t, e, n))
                t = e

    for n, s, e in events:
        close_until(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][0]))
        t = max(t, s)
        stack.append((n, s, e))
    close_until(float("inf"))
    return segs


def _gap_labeller(segs):
    """A function naming the segment label that covers most of a gap,
    or ``"no span"``."""
    starts = [s for s, _, _ in segs]

    def label(gap: tuple[float, float]) -> str:
        cover: dict[str, float] = {}
        k = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
        while k < len(segs) and segs[k][0] < gap[1]:
            s, e, n = segs[k]
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
            k += 1
        return max(cover, key=cover.get) if cover else "no span"

    return label


def _self_times(ops) -> list[float]:
    """Self time of each ``(start, end, name)`` op of one run, in the
    order given (sorted by start, the longer first on a tie): its duration
    less that of the ops nested directly inside it."""
    self_t = [e - s for s, e, _ in ops]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return self_t


@dataclasses.dataclass
class ProgramReduced:
    decode_runs: int
    scope_ms: dict               # scope -> device ms per decode run
    scope_share: dict            # scope -> share of decode device time
    unscoped_ops: list           # [(op name, ms per decode run)] most first
    decode_gaps_ms: list         # device idle between consecutive decode
    #                              runs with no admission or release between
    idle_gaps_program: list      # [(span, seconds)] most first


def reduce(trace: tr.Trace, prog: ProgramTrace, top: int = 10,
           decode_module: str = DECODE_MODULE) -> ProgramReduced:
    w0, w1 = tr.window(trace)
    inner = [s for s in prog.spans if s[0] != tr.WINDOW_SPAN]
    label = _gap_labeller(_innermost_segments(inner))
    n_dev = max(len(trace.ops), 1)
    stops = sorted((s, e) for n, s, e in inner if n in NOT_DECODE)
    stop_starts = [s for s, _ in stops]
    stop_reach = list(itertools.accumulate((e for _, e in stops), max))
    idle: dict[str, float] = {}
    scope_ns: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    gaps: list[float] = []
    runs = 0
    for plane, evs in trace.ops.items():
        busy = tr.merge(tr.clip([(s, e) for _, s, e in evs], w0, w1))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                lab = label((s, e))
                idle[lab] = idle.get(lab, 0.0) + (e - s) / 1e9
        dec = sorted((s, e) for n, s, e in trace.modules.get(plane, ())
                     if n.startswith(decode_module) and s >= w0 and e <= w1)
        runs += len(dec)
        # ops inside decode runs, by scope, at their self time
        ops = sorted(((s, e, op_name(n)) for n, s, e in evs),
                     key=lambda o: (o[0], -o[1]))
        op_starts = [s for s, _, _ in ops]
        for d0, d1 in dec:
            lo = bisect.bisect_left(op_starts, d0)
            hi = bisect.bisect_left(op_starts, d1)
            run = [(s, min(e, d1), n) for s, e, n in ops[lo:hi]]
            for (_, _, n), dur in zip(run, _self_times(run)):
                top_scope, sub = scope_of(prog.op_paths.get(n, ""))
                scope_ns[top_scope] = scope_ns.get(top_scope, 0.0) + dur
                if sub is not None:
                    scope_ns[sub] = scope_ns.get(sub, 0.0) + dur
                if top_scope == UNSCOPED:
                    unscoped[n] = unscoped.get(n, 0.0) + dur
        # idle between consecutive decode runs with no admission or release
        # span starting or running between them
        for (_, a1), (b0, _) in zip(dec, dec[1:]):
            j = bisect.bisect_left(stop_starts, b0) - 1
            if j >= 0 and stop_reach[j] > a1:
                continue
            busy_between = sum(min(e, b0) - max(s, a1) for s, e in busy
                               if e > a1 and s < b0)
            gaps.append((b0 - a1 - busy_between) / 1e6)
    total = sum(v for k, v in scope_ns.items() if k not in SUB_SCOPES)
    per_run = lambda ns: ns / 1e6 / max(runs, 1)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return ProgramReduced(
        decode_runs=runs,
        scope_ms={k: per_run(v) for k, v in scope_ns.items()},
        scope_share={k: v / total for k, v in scope_ns.items()} if total
        else {},
        unscoped_ops=[(n, per_run(v)) for n, v in rank(unscoped)],
        decode_gaps_ms=gaps,
        idle_gaps_program=rank({k: v / n_dev for k, v in idle.items()}))


def median_gap_ms(red: ProgramReduced):
    return statistics.median(red.decode_gaps_ms) if red.decode_gaps_ms \
        else None


def traced_run(cell: str, seed: int, seconds: float, **run_kw):
    """One traced run of ``cell`` through the harness; returns its result
    line and the program's breakdown of the same trace.  ``run_kw`` goes
    to ``harness.run_cell`` (tests: smoke sizes on the CPU)."""
    from . import harness
    seen: dict = {}

    def hook(eng):
        seen["stats"], seen["stats0"] = eng.stats, harness._snapshot(eng)
        seen["hlo"] = eng._decode._jit.lower(
            eng.params, eng.state, eng._class_ids).compile().as_text()

    def load_both(path, *a, **kw):
        seen["prog"] = load(path, seen.get("hlo", ""))
        seen["trace"] = bench_load(path, *a, **kw)
        return seen["trace"]

    bench_load, tr.load = tr.load, load_both
    try:
        out = harness.run_cell(cell, seed, seconds, True, hook=hook, **run_kw)
    finally:
        tr.load = bench_load
    red = reduce(seen["trace"], seen["prog"])
    s0 = seen["stats0"]
    return out, {
        "decode_runs": red.decode_runs,
        "decode_scope_ms": red.scope_ms,
        "decode_scope_share": red.scope_share,
        "decode_unscoped_ops_ms": red.unscoped_ops,
        "decode_host_gap_ms": median_gap_ms(red),
        "decode_gaps": len(red.decode_gaps_ms),
        "idle_gaps_program": red.idle_gaps_program,
        "idle_gaps": tr.reduce(seen["trace"]).idle_by_span,
        "window_compiles": harness._CompileCounter._one.compiles,
        # the engine's counters over the window and its drain
        "stats_delta": {k: v - s0[k] for k, v in
                        dataclasses.asdict(seen["stats"]).items() if k in s0},
    }


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    from .harness import NoChip

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the breakdown here as JSON")
    args = ap.parse_args(argv)
    try:
        out, breakdown = traced_run(args.workload, args.seed, args.seconds)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    text = json.dumps(breakdown)
    print("program breakdown: " + text, flush=True)
    if args.out:
        Path(args.out).write_text(text)
    return 0


if __name__ == "__main__":
    import sys
    _root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]
    raise SystemExit(main())
