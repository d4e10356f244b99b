"""Program spans (``repro.obs``), the engine's host-read and compile
counters, and the decode step's named scopes."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import smoke_config
from repro.models import init_params, make_paged_config
from repro.serve import engine as engine_mod
from repro.serve.engine import ServingEngine, run_admission
from repro.serve.scheduler import Request, Scheduler, make_scheduler_config

#: child span -> the span it must lie inside
PARENT = {
    "repro.admission.plan": "repro.admission",
    "repro.admission.prefill": "repro.admission",
    "repro.admission.kv_install": "repro.admission",
    "repro.admission.readback": "repro.admission",
    "repro.admission.telemetry": "repro.admission",
    "repro.decode.dispatch": "repro.decode",
    "repro.decode.readback": "repro.decode",
    "repro.decode.telemetry": "repro.decode",
    "repro.release.burst": "repro.release",
    "repro.release.telemetry": "repro.release",
}
#: blocking reads ``ServingEngine.step`` makes: failed, bursts, the two
#: stash counters, the burst's tenant stats, the stash histogram, tokens
STEP_READS = 7


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("deepseek-7b")
    params = init_params(cfg, dtype=jnp.float32)
    # shapes no other test uses, so the first admission compiles
    kvcfg = make_paged_config(cfg, seq_len=48, lanes=3, page_size=8,
                              dtype=jnp.float32)
    return cfg, params, kvcfg, make_scheduler_config(cfg, kvcfg)


def _engine(model):
    cfg, params, kvcfg, scfg = model
    return ServingEngine(cfg, kvcfg, params, dtype=jnp.float32,
                         sched_cfg=scfg), Scheduler(scfg)


def _submit(sched, cfg, n, seed=0):
    rng = np.random.RandomState(seed)
    for rid in range(n):
        sched.submit(Request(
            rid=100 + rid, max_new_tokens=3 + rid,
            tokens=rng.randint(0, cfg.vocab_size, 5 + rid).astype(np.int32)))


def _serve(eng, sched) -> None:
    """The serving lifecycle, as ``launch.serve.serve_loop`` runs it."""
    while sched.has_work:
        if sched.waiting and len(sched.running) < sched.scfg.max_lanes:
            run_admission(eng, sched)
        if not sched.running:
            break
        finished = sched.note_decode_step(eng.step())
        if finished:
            eng.release(finished)
            sched.complete(finished)


def _program_spans(log_dir) -> list:
    from jax.profiler import ProfileData
    files = sorted(log_dir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def _ids(text: str) -> list[int]:
    return [int(x) for x in re.findall(r"-?\d+", text)]


def test_spans_nest_and_carry_ids(model, tmp_path):
    cfg = model[0]
    eng, sched = _engine(model)
    _submit(sched, cfg, 5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, sched)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert set(PARENT) | set(PARENT.values()) | {"repro.sched.step"} <= names
    for name, s, e, _ in spans:
        if name in PARENT:
            assert any(p == PARENT[name] and ps <= s and e <= pe
                       for p, ps, pe, _ in spans), name
    # every request's admission names its lane and id; its lane's release
    # follows, so one request's spans can be joined
    admitted = {}
    for name, s, _, st in spans:
        if name == "repro.admission" and "rids" in st:
            lanes, rids = _ids(st["lanes"]), _ids(st["rids"])
            assert len(lanes) == len(rids)
            admitted.update({r: (l, s) for l, r in zip(lanes, rids)})
    assert sorted(admitted) == list(range(100, 105))
    releases = [(_ids(st["lanes"]), s) for n, s, _, st in spans
                if n == "repro.release"]
    for lane, t in admitted.values():
        assert any(lane in ls and s > t for ls, s in releases)
    steps = [st["step"] for n, _, _, st in spans if n == "repro.decode"]
    assert steps == list(range(eng.stats.decode_steps))


def test_host_reads_are_what_step_reads(model, monkeypatch, tmp_path):
    """Every device->host read in ``step`` goes through the counted helper,
    with a trace running or not."""
    from jax._src.array import ArrayImpl
    cfg = model[0]
    eng, sched = _engine(model)
    _submit(sched, cfg, 3)
    run_admission(eng, sched)
    eng.step()                                  # compiled before counting
    inside, reads, fetches = [False], [], []
    fetch, value = ServingEngine._fetch, ArrayImpl._value

    def counted_fetch(self, v):
        fetches.append(1)
        inside[0] = True
        try:
            return fetch(self, v)
        finally:
            inside[0] = False

    def watched_value(self):
        reads.append(inside[0])
        return value.fget(self)

    monkeypatch.setattr(ServingEngine, "_fetch", counted_fetch)
    monkeypatch.setattr(ArrayImpl, "_value", property(watched_value))
    for traced in (False, True):
        del reads[:], fetches[:]
        r0, s0 = eng.stats.decode_host_reads, eng.stats.decode_steps
        if traced:
            jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(2):
                eng.step()
        finally:
            if traced:
                jax.profiler.stop_trace()
        n = eng.stats.decode_steps - s0
        assert reads and all(reads), "a read in step() bypassed _fetch"
        assert len(fetches) == STEP_READS * n
        assert eng.stats.decode_host_reads - r0 == STEP_READS * n


def test_cold_admission_counts_its_compiles(model):
    cfg = model[0]
    eng, sched = _engine(model)
    _submit(sched, cfg, 2, seed=1)
    seen = []

    def listen(event, duration, **kw):
        if event == engine_mod._COMPILE_EVENT:
            seen.append(obs.innermost())

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        run_admission(eng, sched)
        n_admission = len(seen)
        eng.step()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    st = eng.stats
    assert n_admission > 0 and st.prefill_compiles == 1
    # every compile of the pass happened inside one of its spans
    assert all(top is not None and top[0].startswith("repro.admission")
               for top in seen[:n_admission])
    assert st.admission_compiles == n_admission
    assert st.release_compiles == 0
    # the decode step's compile is its own (decode_compiles), not admission's
    assert len(seen) > n_admission and st.decode_compiles == 1


def test_decode_step_named_scopes(model):
    """The compiled decode step's op metadata carries every scope the trace
    reduction attributes device time to."""
    eng, _ = _engine(model)
    lowered = eng._decode._jit.lower(eng.params, eng.state, eng._class_ids)
    op_names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for scope in ("attention", "gather_kv", "mlp", "logits", "alloc",
                  "kv_write"):
        assert any(f"/{scope}/" in n for n in op_names), scope
    # gather_kv sits inside attention
    assert all("/attention/" in n for n in op_names if "/gather_kv/" in n)
