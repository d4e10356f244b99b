"""Shared arithmetic of the metric readers under ``bench/metrics/``."""
from __future__ import annotations

import numpy as np

from . import spec
from .trace import module_time

#: the decode step is the engine's one ``CountingJit``, whose jitted
#: function is ``_wrapped``: XLA names its module ``jit__wrapped``
DECODE_MODULE = "jit__wrapped"


def pct(values, q: float):
    """The ``q``-th percentile (linear interpolation); None when empty."""
    return float(np.percentile(values, q)) if len(values) else None


def itl_ms(run) -> list[float]:
    return [float(g) * 1e3 for r in run.window.records.values()
            for g in np.diff(r.times)]


def delta(run, counter: str) -> float:
    return run.stats1[counter] - run.stats0[counter]


def completed(run) -> int:
    return sum(r.done for r in run.window.records.values())


def decode_device(run):
    """(device seconds, runs) of the decode executable in the trace."""
    if run.trace is None:
        return None
    s, n = module_time(run.trace, DECODE_MODULE)
    return (s, n) if n else None


def decode_step_ms(run):
    dev = decode_device(run)
    return dev[0] / dev[1] * 1e3 if dev else None


def decode_mfu(run):
    dev = decode_device(run)
    steps = run.window.steps
    if not dev or not steps:
        return None
    flops = spec.family(run.model).decode_flops(run.model, steps)
    # the traced runs' mean device time, times the steps the driver made
    return flops / (dev[0] / dev[1] * len(steps) * run.peak["bf16_flops"])


def idle_share(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s

