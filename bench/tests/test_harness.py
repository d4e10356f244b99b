"""The harness end to end at smoke size on the CPU: the result line, the
refusals without a chip, and ``correct`` failing when the timed path is
broken underneath."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.tests import smoke

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
CELL = "deepseek-7b.long-decode-sat"
SEED = 2**40 + 12345


@pytest.mark.parametrize("cell", CELLS)
def test_result_line(cell):
    out = json.loads(json.dumps(smoke.run(cell, SEED)))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in spec.load_cell(cell).end_to_end}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert out["device"]["platform"] == "cpu"
    for k, c in out["checks"].items():
        assert c["value"] <= c["limit"], k


def test_traced_line():
    out = smoke.run(CELL, SEED + 1, trace=True)
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    per_layer = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert {"admission_share", "lane_occupancy", "alloc_bursts_per_req",
            "kv_pool_peak_share"} <= set(out["metrics"]) <= per_layer


def test_open_loop():
    """The open-loop driver (requests due at seeded Poisson times, timed
    from their due time) through a cell's harness."""
    mix = smoke.smoke_mix(CELL)
    mix.update(loop="open", arrival={"kind": "poisson", "rate_per_s": 8.0})
    mix.pop("clients")
    out = smoke.run(CELL, SEED + 3, mix_spec=mix)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 8       # 8 req/s over a 1 s window


def test_prefix_mix():
    """A mix with shared system prompts and the prefix cache on: set-up
    warms the cache, the window admits through cached pages, and the
    sample compared with the reference holds cache hits."""
    mix = smoke.smoke_mix(CELL)
    mix.update(prefix={"count": 2, "tokens": 16, "zipf_s": 1.0},
               prefix_cache=True)
    engines = []
    out = smoke.run(CELL, SEED + 4, mix_spec=mix,
                    hook=engines.append)
    assert out["correct"] is True
    assert engines[0].stats.prefill_tokens_saved > 0


def _run_cmd(cwd) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_no_result():
    r = _run_cmd(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cmd(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


# ---- faults planted under the timed path --------------------------------

def _wrap_decode(eng, fn):
    inner = eng._decode

    def step(params, state, class_ids):
        return fn(state, *inner(params, state, class_ids))
    step.compiles, step.compile_us = 1, 0.0
    eng._decode = step


def state_unchanged(eng):
    """The decode step hands back the state it was given."""
    _wrap_decode(eng, lambda old, new, logits, stats: (old, logits, stats))


def half_batch(eng):
    """The upper half of the lanes is left out of every step."""
    lanes = eng.kvcfg.max_lanes
    keep = jnp.arange(lanes) < lanes // 2

    def fn(old, new, logits, stats):
        toks = jnp.where(keep, new.tokens, old.tokens)
        return new._replace(tokens=toks), logits, stats
    _wrap_decode(eng, fn)


def token_altered(eng):
    """One served token per step is changed where the step produces it."""
    inner, vocab = eng.step, eng.cfg.vocab_size

    def step():
        toks = np.array(inner())
        toks[0] = (toks[0] + 1) % vocab
        return toks
    eng.step = step


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(fault):
    out = smoke.run(CELL, SEED + 2, hook=fault)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]
