"""Wall-clock load driver over the program's serving lifecycle.

It drives ``ServingEngine`` + ``Scheduler`` exactly as the program's
``serve_loop`` does (``run_admission`` -> ``eng.step()`` ->
``eng.release()``), and stamps every request on the host clock:

* ``due``     when the request was due (open loop: its seeded arrival;
              closed loop: when its client's previous request completed),
* ``submit``  when the driver handed it to the scheduler,
* ``admit``   when the admission pass that took it began,
* ``times``   when each output token was available on the host: the
              first when its admission pass returned, every later one
              when the decode step that made it returned.

All stamps are seconds after the window opened.  With ``annotate`` the
calls into each layer are wrapped in ``jax.profiler.TraceAnnotation``
spans named ``bench.gen``, ``bench.admission``, ``bench.decode``,
``bench.release`` and ``bench.wait``, inside one ``bench.window`` span.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

SPANS = ("bench.gen", "bench.admission", "bench.decode", "bench.release",
         "bench.wait")


@dataclasses.dataclass(eq=False)
class Record:
    rid: int
    due: float
    prompt_len: int
    max_new_tokens: int
    prefix_id: int = -1
    submit: float = float("nan")
    admit: float = float("nan")
    cached_len: int = 0
    times: list = dataclasses.field(default_factory=list)
    tokens: np.ndarray | None = None     # prompt
    output: list | None = None           # served tokens
    done: bool = False


@dataclasses.dataclass
class Step:
    t: float          # when the step's tokens reached the host
    active: int       # lanes that made a token
    kv_tokens: int    # sum over those lanes of the positions they attend
    kv_lens: tuple    # the positions each of those lanes attends


@dataclasses.dataclass
class WindowResult:
    records: dict
    steps: list
    seconds: float        # the window's length
    end: float            # when the drain finished
    admissions: list      # (start, end) of every admission pass
    withdrawn: int = 0    # closed loop: waiting requests dropped at close


def _span(annotate: bool):
    if annotate:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


class Driver:
    def __init__(self, eng, sched, annotate: bool = False):
        from repro.serve.engine import run_admission
        from repro.serve.scheduler import Request
        self.eng, self.sched = eng, sched
        self._admission, self._request = run_admission, Request
        self.span = _span(annotate)
        self.lanes = sched.scfg.max_lanes
        self.kv_peak = None       # most KV pages in use after any step
        self._pool_class = None
        self.admissions: list[tuple[float, float]] = []

    def track_pool(self, size_class: int) -> None:
        """Read the KV class's used pages after every step (one more
        device read per step: traced runs only)."""
        self._pool_class, self.kv_peak = size_class, 0

    # ---- one pass of each lifecycle stage --------------------------------

    def submit(self, item, rec: Record, now: float) -> None:
        req = self._request(rid=item.rid, tokens=item.tokens,
                            max_new_tokens=item.max_new_tokens)
        self.sched.submit(req)
        rec.submit = now

    def admit(self, recs: dict, t0: float) -> None:
        before = set(self.sched.running)
        ta = time.perf_counter() - t0
        with self.span("bench.admission"):
            self._admission(self.eng, self.sched)
        tb = time.perf_counter() - t0
        self.admissions.append((ta, tb))
        for lane, req in self.sched.running.items():
            if lane not in before:
                rec = recs[req.rid]
                rec.admit, rec.cached_len = ta, int(req.cached_len)
                rec.times.append(tb)

    def decode(self, recs: dict, t0: float, steps: list) -> list:
        """One decode step; returns the requests it completed."""
        running = list(self.sched.running.values())
        with self.span("bench.decode"):
            toks = self.eng.step()
            tc = time.perf_counter() - t0
            lens = []
            for req in running:
                recs[req.rid].times.append(tc)
                lens.append(req.prompt_len + len(req.output))
            steps.append(Step(tc, len(running), sum(lens), tuple(lens)))
            finished = self.sched.note_decode_step(toks)
            if self._pool_class is not None:
                self.kv_peak = max(self.kv_peak, int(
                    self.eng.state.paged.alloc.used[self._pool_class]))
        if not finished:
            return []
        with self.span("bench.release"):
            kv_toks = {ln: self.sched.kv_token_prefix(ln) for ln in finished} \
                if self.eng.cache is not None else None
            self.eng.release(finished, kv_tokens=kv_toks)
            done = self.sched.complete(finished)
        for req in done:
            rec = recs[req.rid]
            rec.done, rec.tokens = True, req.tokens
            rec.output = list(req.output)
        return done

    # ---- loops -----------------------------------------------------------

    def open_loop(self, items, seconds: float, grace: float) -> WindowResult:
        """Submit each item at its due time; serve until all are done or
        ``grace`` seconds after the window closes."""
        recs = {it.rid: Record(it.rid, it.due, len(it.tokens),
                               it.max_new_tokens, it.prefix_id)
                for it in items}
        steps: list = []
        sched = self.sched
        i, n = 0, len(items)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if i < n and items[i].due <= now:
                with self.span("bench.gen"):
                    while i < n and items[i].due <= now:
                        self.submit(items[i], recs[items[i].rid],
                                    time.perf_counter() - t0)
                        i += 1
            if sched.waiting and len(sched.running) < self.lanes:
                self.admit(recs, t0)
            if sched.running:
                self.decode(recs, t0, steps)
            elif i >= n and not sched.waiting:
                break
            elif i < n and not sched.waiting:
                with self.span("bench.wait"):
                    wait = items[i].due - (time.perf_counter() - t0)
                    if wait > 0:
                        time.sleep(min(wait, 0.002))
            if now > seconds + grace:
                break
        return WindowResult(recs, steps, seconds, time.perf_counter() - t0,
                            self.admissions)

    def closed_loop(self, items, clients: int, seconds: float,
                    grace: float) -> WindowResult:
        """``clients`` each send their next item when the last completes;
        at the close the waiting ones are withdrawn and the running ones
        drain."""
        recs: dict = {}
        steps: list = []
        sched = self.sched
        nxt = iter(items)
        t0 = time.perf_counter()

        def send(now):
            it = next(nxt)
            rec = recs[it.rid] = Record(it.rid, now, len(it.tokens),
                                        it.max_new_tokens, it.prefix_id)
            self.submit(it, rec, now)

        with self.span("bench.gen"):
            for _ in range(clients):
                send(0.0)
        closed = False
        withdrawn = 0
        while True:
            now = time.perf_counter() - t0
            if not closed and now >= seconds:
                closed = True
                withdrawn = len(sched.waiting)
                for req in sched.waiting:
                    recs.pop(req.rid)
                sched.waiting.clear()
            if sched.waiting and len(sched.running) < self.lanes:
                self.admit(recs, t0)
            if sched.running:
                done = self.decode(recs, t0, steps)
                if done and not closed:
                    with self.span("bench.gen"):
                        tn = time.perf_counter() - t0
                        for _ in done:
                            send(tn)
            elif closed or not sched.waiting:
                break
            if now > seconds + grace:
                break
        return WindowResult(recs, steps, seconds, time.perf_counter() - t0,
                            self.admissions, withdrawn)
