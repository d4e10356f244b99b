"""The program's breakdown of a trace: decode device time by named scope,
the idle gap between decode runs, idle gaps labelled by the innermost
``repro.*`` span."""
import jax
import pytest

from bench import program_trace as pt
from bench import trace as tr

MS = 1e6
DECODE = "jit__wrapped(7)"


def _trace() -> tuple[tr.Trace, pt.ProgramTrace]:
    """Two decode runs, an admission between the second and the third, a
    release after the third; gaps on the device in between."""
    # each run: a loop whose body holds fusion.1 and fusion.2, then two
    # ops; the trace names an op by its HLO text
    run = [("%while.5 = while(...)", 0, 6), ("%fusion.1 = fusion(...)", .5, 4),
           ("%fusion.2", 4, 6), ("%copy.3", 6, 7), ("fusion.4", 7, 10)]
    at = lambda t: [(n, s + t, e + t) for n, s, e in run]
    ops = at(0) + at(20) + [("prefill.1", 40, 44)] + at(60)
    runs = [(DECODE, 0, 10), (DECODE, 20, 30), ("jit_prefill(3)", 40, 44),
            (DECODE, 60, 70)]
    bench_spans = [("bench.window", 0, 100), ("bench.decode", 0, 18),
                   ("bench.decode", 18, 31), ("bench.admission", 31, 58),
                   ("bench.decode", 58, 72), ("bench.release", 72, 100)]
    prog_spans = [("repro.decode", 0, 17), ("repro.decode.dispatch", 0, 1),
                  ("repro.decode.readback", 1, 12),
                  ("repro.decode.telemetry", 12, 17),
                  ("repro.decode", 18, 31),
                  ("repro.decode.readback", 19, 31),
                  ("repro.admission", 32, 57),
                  ("repro.admission.prefill", 33, 45),
                  ("repro.admission.kv_install", 45, 56),
                  ("repro.decode", 58, 71),
                  ("repro.decode.readback", 59, 71),
                  ("repro.release", 73, 99),
                  ("repro.release.burst", 74, 98)]
    ms = lambda evs: [(n, s * MS, e * MS) for n, s, e in evs]
    trace = tr.Trace(ops={"/device:TPU:0": ms(ops)},
                     modules={"/device:TPU:0": ms(runs)},
                     spans=ms(bench_spans))
    paths = {"fusion.1": "jit(_wrapped)/while/body/attention/gather_kv/gather",
             "fusion.2": "jit(_wrapped)/while/body/mlp/dot_general",
             "fusion.4": "jit(_wrapped)/alloc/scatter"}
    return trace, pt.ProgramTrace(spans=ms(bench_spans + prog_spans),
                                  op_paths=paths)


def test_scope_attribution():
    """Each op counts at its self time: the loop's own 0.5 ms, not the
    6 ms its event spans around its body's ops."""
    red = pt.reduce(*_trace())
    assert red.decode_runs == 3
    assert red.scope_ms == pytest.approx({
        "attention": 3.5, "gather_kv": 3.5, "mlp": 2.0, "alloc": 3.0,
        "unscoped": 1.5})
    assert red.scope_share["attention"] == pytest.approx(0.35)
    assert red.unscoped_ops == [("copy.3", pytest.approx(1.0)),
                                ("while.5", pytest.approx(0.5))]


def test_self_times_nest():
    ops = [(0, 10, "while"), (0, 4, "a"), (1, 2, "b"), (4, 9, "c"),
           (10, 12, "d")]
    assert pt._self_times(ops) == [1, 3, 1, 5, 2]


def test_gap_between_decode_runs_skips_admission():
    red = pt.reduce(*_trace())
    # runs 1-2 are 10 ms apart; runs 2-3 have an admission between them
    assert red.decode_gaps_ms == pytest.approx([10.0])
    assert pt.median_gap_ms(red) == pytest.approx(10.0)


def test_idle_gaps_labelled_by_innermost_span():
    trace, prog = _trace()
    red = pt.reduce(trace, prog)
    # idle: [10,20] readback 10-12, telemetry 12-17, bench.decode 17-18,
    # decode 18-19, readback 19-20 -> telemetry covers most;
    # [30,40]: readback 30-31, bench.admission 31-32, admission 32-33,
    # prefill 33-40 -> prefill; [44,60]: prefill 44-45, kv_install 45-56,
    # admission 56-57, bench.admission 57-58, bench.decode 58, decode
    # 58-59, readback 59-60 -> kv_install; [70,100]: release.burst most
    assert dict(red.idle_gaps_program) == pytest.approx({
        "repro.decode.telemetry": 0.010, "repro.admission.prefill": 0.010,
        "repro.admission.kv_install": 0.016,
        "repro.release.burst": 0.030})
    # the same gaps, by the driver's spans, are unchanged
    assert dict(tr.reduce(trace).idle_by_span) == pytest.approx({
        "bench.decode": 0.010, "bench.admission": 0.026,
        "bench.release": 0.030})


def test_no_program_spans_falls_back_to_the_drivers():
    trace, prog = _trace()
    prog.spans = [s for s in prog.spans if s[0].startswith("bench.")]
    red = pt.reduce(trace, prog)
    assert {n for n, _ in red.idle_gaps_program} <= {
        "bench.decode", "bench.admission", "bench.release"}
    assert red.decode_gaps_ms == pytest.approx([10.0, 30.0 - 4.0])


def test_innermost_segments_nest():
    spans = [("a", 0, 10), ("a.b", 2, 5), ("a.c", 5, 8), ("d", 12, 13)]
    assert pt._innermost_segments(spans) == [
        (0, 2, "a"), (2, 5, "a.b"), (5, 8, "a.c"), (8, 10, "a"),
        (12, 13, "d")]


def test_op_paths_from_compiled_hlo():
    def f(x):
        with jax.named_scope("attention"):
            y = jax.numpy.tanh(x @ x)
        with jax.named_scope("mlp"):
            return (y * 2).sum()
    text = jax.jit(f).lower(jax.numpy.ones((8, 8))).compile().as_text()
    paths = pt.op_paths_from_hlo(text)
    scopes = {pt.scope_of(p)[0] for p in paths.values()}
    assert {"attention", "mlp"} <= scopes


def test_load_recorded_program_spans(tmp_path):
    from repro import obs
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.span("repro.decode", step=3):
            with obs.span("repro.decode.dispatch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    prog = pt.load(tr.find_xplane(tmp_path))
    names = [n for n, _, _ in prog.spans]
    assert names.count("repro.decode") == 1
    assert names.count("repro.decode.dispatch") == 1
    assert "bench.window" in names


def test_traced_run_smoke():
    """The tool's run on the CPU at smoke size: the result line as the
    harness gives it, and the engine's counters over the same window."""
    from bench.tests import smoke
    cell = "deepseek-7b.long-decode-sat"
    out, bd = pt.traced_run(cell, 2**40 + 99, 1.0, require_tpu=False,
                            compile_cache=False,
                            model=smoke.smoke_model(cell),
                            mix_spec=smoke.smoke_mix(cell))
    assert out["correct"] is True
    assert out["metrics"]["host_syncs_per_step"]["value"] == 7.0
    d = bd["stats_delta"]
    assert d["decode_host_reads"] == 7 * d["decode_steps"] > 0
    # every compile of the window happened inside an admission or release
    assert d["admission_compiles"] + d["release_compiles"] \
        == bd["window_compiles"] > 0
