"""The one traffic generator: a mix file under ``bench/traffic/`` in, a
seeded list of requests out.

A mix file is JSON with these keys (see the files beside this module):

``loop``          ``"open"`` (requests due at seeded times) or ``"closed"``
                  (``clients`` each send their next request when the last
                  one completes).
``arrival``       open loop: ``{"kind": "poisson", "rate_per_s": r}``, or
                  ``"bursty"`` (``rate_per_s``, ``burst_factor``,
                  ``dwell_s``), or ``"diurnal"`` (``rate_per_s``,
                  ``amplitude``, ``period_s``).
``clients``       closed loop: number of clients.
``prompt``        length law of the prompt (or of the user suffix when a
                  ``prefix`` is given): ``{"dist": "bounded_pareto",
                  "alpha", "lo", "hi"}``, ``{"dist": "uniform", "lo",
                  "hi"}`` or ``{"dist": "fixed", "lo"}``.
``output``        length law of the generated tokens, same forms.
``prefix``        ``null``, or ``{"count": k, "tokens": n, "zipf_s": s}``:
                  every prompt opens with one of ``k`` system prompts of
                  ``n`` tokens, drawn Zipf(s).
``prefix_cache``  whether the engine runs with its prefix cache on.

Poisson gaps, lengths and system-prompt picks are stratified: every seed
gets the same multiset, in an order and with token ids that the seed
chooses.  In a closed loop, whose window takes some first part of a long
pool, each consecutive block of ``clients`` requests is such a multiset.
Bursty and diurnal arrivals are drawn from the seed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from . import arrivals as arr

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
#: closed-loop request pool; far more than a window can complete
CLOSED_POOL = 8192


@dataclasses.dataclass
class Item:
    rid: int
    due: float            # seconds after the window opens (0 in closed loop)
    tokens: np.ndarray    # [T] int32 prompt
    max_new_tokens: int
    prefix_id: int = -1   # system prompt index, -1 when none


@dataclasses.dataclass
class Mix:
    name: str
    spec: dict
    items: list[Item]

    @property
    def loop(self) -> str:
        return self.spec["loop"]

    @property
    def clients(self) -> int:
        return int(self.spec.get("clients", 0))

    @property
    def prefix_cache(self) -> bool:
        return bool(self.spec.get("prefix_cache", False))


def load_spec(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _lengths(law: dict, u: np.ndarray) -> np.ndarray:
    dist = law["dist"]
    if dist == "bounded_pareto":
        return arr.bounded_pareto_quantiles(u, law["alpha"], law["lo"],
                                            law["hi"])
    if dist == "uniform":
        return arr.uniform_quantiles(u, law["lo"], law["hi"])
    if dist == "fixed":
        return np.full(u.shape, int(law["lo"]), np.int64)
    raise ValueError(f"unknown length law {dist!r}")


def max_length(law: dict) -> int:
    return int(law.get("hi", law["lo"]))


def max_prompt_len(spec: dict) -> int:
    pre = spec.get("prefix") or {}
    return int(pre.get("tokens", 0)) + max_length(spec["prompt"])


def max_seq_len(spec: dict) -> int:
    return max_prompt_len(spec) + max_length(spec["output"])


def _arrival_times(spec: dict, seconds: float,
                   rng: np.random.Generator) -> np.ndarray:
    a = spec["arrival"]
    rate = float(a["rate_per_s"])
    if a["kind"] == "poisson":
        n = int(rate * seconds)
        gaps = arr.exponential_quantiles(arr.stratified(n), rate)
        return np.cumsum(rng.permutation(gaps))
    n = int(4 * rate * seconds) + 16
    if a["kind"] == "bursty":
        t, _ = arr.bursty_arrivals(n, rate, rate * a["burst_factor"],
                                   a["dwell_s"], rng)
    elif a["kind"] == "diurnal":
        t = arr.diurnal_arrivals(n, rate, a["amplitude"], a["period_s"], rng)
    else:
        raise ValueError(f"unknown arrival kind {a['kind']!r}")
    return t[t < seconds]


def build(name: str, vocab_size: int, seed: int, seconds: float,
          spec: dict | None = None) -> Mix:
    """The cell's requests for one run, deterministic in ``seed``."""
    spec = spec if spec is not None else load_spec(name)
    rng = np.random.default_rng(seed)
    if spec["loop"] == "open":
        due = _arrival_times(spec, seconds, rng)
    elif spec["loop"] == "closed":
        due = np.zeros(CLOSED_POOL)
    else:
        raise ValueError(f"unknown loop {spec['loop']!r}")
    n = len(due)
    block = int(spec["clients"]) if spec["loop"] == "closed" else max(n, 1)
    u = np.tile(arr.stratified(block), -(-n // block))[:n]

    def draw(values):       # a seeded order within each block
        return np.concatenate([values[:0]] + [
            rng.permutation(values[i:i + block]) for i in range(0, n, block)])
    plen = draw(_lengths(spec["prompt"], u))
    olen = draw(_lengths(spec["output"], u))
    pre = spec.get("prefix")
    if pre:
        systems = rng.integers(0, vocab_size, (pre["count"], pre["tokens"]),
                               dtype=np.int32)
        pick = draw(arr.zipf_quantiles(u, pre["count"], pre["zipf_s"]))
    items = []
    for i in range(n):
        toks = rng.integers(0, vocab_size, int(plen[i]), dtype=np.int32)
        pid = -1
        if pre:
            pid = int(pick[i])
            toks = np.concatenate([systems[pid], toks])
        items.append(Item(rid=i, due=float(due[i]), tokens=toks,
                          max_new_tokens=int(olen[i]), prefix_id=pid))
    return Mix(name=name, spec=spec, items=items)
