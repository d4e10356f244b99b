"""The reduction from a profiler trace to busy time, labelled idle gaps
and per-module device time."""
import jax
import numpy as np
import pytest

from bench import trace as tr


def _synthetic() -> tr.Trace:
    ms = 1e6
    return tr.Trace(
        ops={"/device:TPU:0": [("fusion.1", 0 * ms, 10 * ms),
                               ("fusion.2", 5 * ms, 20 * ms),
                               ("copy.3", 60 * ms, 70 * ms),
                               ("fusion.1", 90 * ms, 120 * ms)]},
        modules={"/device:TPU:0": [("jit__wrapped(7)", 0 * ms, 20 * ms),
                                   ("jit_prefill(3)", 60 * ms, 70 * ms),
                                   ("jit__wrapped(7)", 90 * ms, 120 * ms)]},
        spans=[("bench.window", 0 * ms, 100 * ms),
               ("bench.decode", 0 * ms, 22 * ms),
               ("bench.admission", 22 * ms, 58 * ms),
               ("bench.decode", 58 * ms, 100 * ms)])


def test_merge_and_clip():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert tr.clip([(0, 4), (5, 10)], 3, 6) == [(3, 4), (5, 6)]


def test_reduce_synthetic():
    red = tr.reduce(_synthetic())
    assert red.window_s == pytest.approx(0.1)
    # busy: [0, 20] + [60, 70] + [90, 100] inside the window
    assert red.busy_s == pytest.approx(0.040)
    idle = dict(red.idle_by_span)
    # a whole gap goes to the span that covers most of it: [20, 60] to
    # admission, [70, 90] to decode
    assert idle == pytest.approx({"bench.admission": 0.040,
                                  "bench.decode": 0.020})
    s, n = tr.module_time(red, "jit__wrapped")
    assert n == 2 and s == pytest.approx(0.050)
    assert red.top_ops[0][0] == "fusion.1"


def test_reduce_needs_window():
    t = _synthetic()
    t.spans = [s for s in t.spans if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_load_recorded_host_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.decode"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(tmp_path))
    names = [n for n, _, _ in t.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.decode") == 2
    w0, w1 = tr.window(t)
    assert all(w0 <= s and e <= w1 for n, s, e in t.spans
               if n == "bench.decode")


def _label_by_scan(gap, spans) -> str:
    best, name = 0.0, "no span"
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def test_labeller_matches_a_scan_of_every_span():
    rng = np.random.default_rng(3)
    # overlapping, nested and touching spans, on a coarse grid for ties
    spans = [(f"bench.s{i % 5}", float(s), float(s + d)) for i, (s, d) in
             enumerate(zip(rng.integers(0, 200, 300),
                           rng.integers(0, 12, 300)))]
    label = tr._labeller(spans)
    for s, d in zip(rng.integers(-5, 210, 2000), rng.integers(0, 15, 2000)):
        gap = (float(s), float(s + d))
        assert label(gap) == _label_by_scan(gap, spans)
