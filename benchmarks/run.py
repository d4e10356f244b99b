"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV rows; run as
``PYTHONPATH=src python -m benchmarks.run [--only fig09]``.
"""
import argparse
import inspect
import sys

from . import (fig08_single_thread, fig09_multithread, fig10_l2_miss,
               fig11_atomics, fig12_memory, fig13_energy,
               fig14_l1d_sensitivity, fig15_cache_partition,
               fig16_l2_capacity, fig17_icmalloc, roofline_report,
               table3_speedups)

MODULES = {
    "fig08": fig08_single_thread,
    "fig09": fig09_multithread,
    "table3": table3_speedups,
    "fig10": fig10_l2_miss,
    "fig11": fig11_atomics,
    "fig12": fig12_memory,
    "fig13": fig13_energy,
    "fig14": fig14_l1d_sensitivity,
    "fig15": fig15_cache_partition,
    "fig16": fig16_l2_capacity,
    "fig17": fig17_icmalloc,
    "roofline": roofline_report,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, help="comma-separated module keys")
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed threaded into seed-aware modules and "
                         "recorded in their json output")
    args = ap.parse_args()
    keys = args.only.split(",") if args.only else list(MODULES)
    print("name,us_per_call,derived")
    failures = 0
    for key in keys:
        try:
            mod_run = MODULES[key].run
            rows = mod_run(seed=args.seed) \
                if "seed" in inspect.signature(mod_run).parameters \
                else mod_run()
            for row in rows:
                print(row)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{key},0,ERROR {type(e).__name__}: {e}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
