"""Read a cell's compared numbers for the program and for its control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Runs the cell once per seed in this one process (the benchmark's own runs
never run the control) with the control in the program's place, and
prints per seed the control's ``correct`` (it has to come out false), its
``logit_gap`` (the gap of the token that the float8 reference puts first)
and the program's own on the same sample.  These readings set the limit
in ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--quant", default="fp8")
    args = ap.parse_args(argv)
    from bench.harness import run_cell
    rows = []
    for seed in args.seeds:
        out = run_cell(args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), control=args.quant)
        row = {"seed": seed, "control_correct": out["correct"],
               "program": out["control"]["program_logit_gap"],
               "control": out["checks"]["logit_gap"]["value"],
               "attempted": out["attempted"], "failed": out["failed"],
               "other_checks": {k: v["value"] for k, v in out["checks"].items()
                                if k != "logit_gap"}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows]
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": max(prog), "control_min": min(ctrl),
                      "program": prog, "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
