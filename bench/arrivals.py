"""Seeded arrival processes and length samplers, with times in SECONDS.

Copied from ``repro.loadgen.arrivals`` (whose clock is virtual decode
steps) so that later changes to the program cannot move the yardstick.
Rates here are arrivals per second of wall clock.

The ``*_quantiles`` helpers give a fixed multiset of values for ``n``
stratified probabilities: a cell draws the same set of gaps and lengths
for every seed and lets the seed choose only their order, so two seeds
offer the same work.
"""
from __future__ import annotations

import numpy as np


def stratified(n: int) -> np.ndarray:
    """``[n]`` probabilities ``(i + 0.5) / n``: one per equal-mass stratum."""
    return (np.arange(n) + 0.5) / n


def exponential_quantiles(u: np.ndarray, rate: float) -> np.ndarray:
    """Inter-arrival gaps (s) of a Poisson process at probabilities ``u``."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    return -np.log1p(-u) / rate


def bounded_pareto_quantiles(u: np.ndarray, alpha: float, lo: int,
                             hi: int) -> np.ndarray:
    """Integer lengths of a bounded Pareto(alpha) on ``[lo, hi]`` at
    probabilities ``u`` (inverse CDF)."""
    if not lo <= hi:
        raise ValueError(f"need lo <= hi, got {lo} > {hi}")
    r = (lo / hi) ** alpha
    x = lo / (1.0 - u * (1.0 - r)) ** (1.0 / alpha)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def uniform_quantiles(u: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Integer lengths uniform on ``[lo, hi]`` at probabilities ``u``."""
    return np.clip(lo + np.floor(u * (hi - lo + 1)), lo, hi).astype(np.int64)


def zipf_quantiles(u: np.ndarray, k: int, s: float) -> np.ndarray:
    """Ranks ``0..k-1`` of a Zipf(s) law over ``k`` items at ``u``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, u, side="right"), k - 1)


# ---- copies of repro.loadgen.arrivals, re-expressed in seconds ----------

def poisson_arrivals(n: int, rate: float,
                     rng: np.random.Generator) -> np.ndarray:
    """``[n]`` arrival times (s) of a Poisson process of ``rate`` per s."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def bursty_arrivals(n: int, rate_lo: float, rate_hi: float, dwell: float,
                    rng: np.random.Generator
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Two-state Markov-modulated Poisson process; ``dwell`` is the mean
    seconds spent in a regime.  Returns ``(times, regimes)``."""
    if min(rate_lo, rate_hi) <= 0 or dwell <= 0:
        raise ValueError("rates and dwell must be positive")
    times = np.empty(n)
    regimes = np.empty(n, np.int32)
    t, regime = 0.0, 0
    for i in range(n):
        gap = rng.exponential(1.0 / (rate_hi if regime else rate_lo))
        t += gap
        times[i] = t
        regimes[i] = regime
        if rng.uniform() < 1.0 - np.exp(-gap / dwell):
            regime = 1 - regime
    return times, regimes


def diurnal_arrivals(n: int, base_rate: float, amplitude: float,
                     period: float, rng: np.random.Generator) -> np.ndarray:
    """Sinusoidally modulated Poisson process (period in s), by thinning."""
    if not 0.0 <= amplitude < 1.0:
        raise ValueError(f"amplitude must be in [0, 1), got {amplitude}")
    if base_rate <= 0 or period <= 0:
        raise ValueError("base_rate and period must be positive")
    lam_max = base_rate * (1.0 + amplitude)
    times = np.empty(n)
    t, i = 0.0, 0
    while i < n:
        t += rng.exponential(1.0 / lam_max)
        lam = base_rate * (1.0 + amplitude * np.sin(2 * np.pi * t / period))
        if rng.uniform() * lam_max < lam:
            times[i] = t
            i += 1
    return times
