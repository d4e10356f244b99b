"""Smoke-size stand-ins for the cells, for tests on the CPU."""
from __future__ import annotations

from bench import spec, traffic

SMOKE_SERVING = {"lanes": 2, "seq_len": 64, "page_size": 16,
                 "dtype": "bfloat16"}


def smoke_model(cell: str) -> dict:
    """The cell's configuration cut to smoke size by its family's
    ``SMOKE``, served on two lanes."""
    model = spec.load_cell(cell).model
    return dict(model, **spec.family(model).SMOKE, serving=SMOKE_SERVING)


def smoke_mix(cell: str) -> dict:
    mix = traffic.load_spec(spec.load_cell(cell).traffic)
    mix = dict(mix, prompt=dict(mix["prompt"], lo=8, hi=24),
               output=dict(mix["output"], lo=4, hi=12))
    if mix["loop"] == "open":
        mix["arrival"] = {"kind": "poisson", "rate_per_s": 8.0}
    else:
        mix["clients"] = 4
    if mix.get("prefix"):
        mix["prefix"] = dict(mix["prefix"], count=2, tokens=16)
    return mix


def run(cell: str, seed: int, seconds: float = 1.0, trace: bool = False,
        hook=None, mix_spec: dict | None = None) -> dict:
    from bench.harness import run_cell
    return run_cell(cell, seed, seconds, trace, require_tpu=False,
                    compile_cache=False, model=smoke_model(cell),
                    mix_spec=mix_spec or smoke_mix(cell), hook=hook)
