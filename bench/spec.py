"""Find a cell's pieces by name: ``BENCHMARK.json`` at the repository
root names the cell's configuration and mix, and each lives in a file of
its own under ``bench/``."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"

#: HF config key -> repro ArchConfig field, for the keys a cell may set
HF_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}
#: what the program's dense decoder fixes and the file has to state
PROGRAM_FIXED = {"rms_norm_eps": 1e-6, "hidden_act": "silu",
                 "tie_word_embeddings": False}


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


def arch_config(model: dict):
    """The program's ``ArchConfig`` for a configuration file, with every
    key the file states applied to the registered architecture."""
    from repro.configs.base import get_config
    for key, want in PROGRAM_FIXED.items():
        if model[key] != want:
            raise ValueError(f"{key}={model[key]!r}: the program runs "
                             f"{want!r} only")
    base = get_config(model["arch"])
    if base.family != "dense" or base.attn_pattern != "full":
        raise ValueError(f"{model['arch']}: the reference is a dense decoder "
                         f"with full attention")
    return dataclasses.replace(
        base, **{f: model[k] for k, f in HF_FIELDS.items() if k in model})
