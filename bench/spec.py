"""Find a cell's pieces by name: ``BENCHMARK.json`` at the repository
root names the cell's configuration and mix, and each lives in a file of
its own under ``bench/``.

What the benchmark knows of a model's shape lives in one module per
family, ``bench/families/<family>.py``, which ``family`` loads by path
from the configuration file's ``"family"`` key.  There is no default: a
file without the key, or one that names a family with no file, is
refused.  A family module provides

* ``arch_config(model)``: the program's ``ArchConfig`` for the
  configuration file, refusing what the program cannot run as stated;
* ``layout(model)``: the weight leaves as ``(name, shape)``, under the
  names of the program's parameter tree (``bench/weights.py`` draws them:
  1-D leaves and names holding ``norm`` or ``/ln_`` are gains stored as
  ``gain - 1``, ``embed`` has std 0.02, every other matrix std
  ``1/sqrt(shape[-2])``);
* ``forward_gaps(model, weights, seqs, targets, pad_to, block, quant)``:
  the plain float32 reference, built on ``bench/reference.py``; per
  sequence, each target's gap below the best logit and the token it
  puts first, and with ``quant="fp8"`` the control;
* ``decode_flops(model, steps)``: model FLOPs of the decode steps, a list
  of ``bench.driver.Step`` (``kv_lens`` gives each lane's positions, for
  layers that attend fewer than all of them);
* ``SMOKE``: configuration keys that cut it to the size of the CPU tests.

So a model of a new family joins the benchmark as new files: its family
module, its configuration, and its cells' traffic and limits.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"

#: one module per model family, found by the configuration's "family"
FAMILY_DIR = BENCH_DIR / "families"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    model: dict                # the configuration file
    end_to_end: list[dict]     # metrics this cell reports with --trace 0
    per_layer: list[dict]      # metrics this cell reports with --trace 1
    limits: dict               # bench/limits/<cell>.json


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    model = json.loads((ROOT / conf["file"]).read_text())
    limits = json.loads((BENCH_DIR / "limits" / f"{name}.json").read_text())
    return Cell(name=name, config_name=w["config"], traffic=w["traffic"],
                chips=int(w["chips"]), model=model,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                limits=limits)


def family(model: dict):
    """The module ``<FAMILY_DIR>/<family>.py`` that the configuration
    file's ``"family"`` key names."""
    files = sorted(p.name for p in FAMILY_DIR.glob("*.py"))
    name = model.get("family")
    if f"{name}.py" not in files:
        raise ValueError(f"configuration {model.get('arch')!r}: family "
                         f"{name!r} has no file under {FAMILY_DIR}; the "
                         f"files there: {files}")
    return _load_family(FAMILY_DIR / f"{name}.py")


@functools.cache
def _load_family(path: Path):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.families.{path.stem}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
