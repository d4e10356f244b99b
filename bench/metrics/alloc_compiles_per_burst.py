"""Executables compiled or loaded from the persistent compile cache inside
the engine's admission and release spans, per admission or release burst,
across the window and its drain.  None where the program does not count
them."""
from bench import stats


def read(run):
    if "admission_compiles" not in run.stats1:
        return None
    bursts = sum(stats.delta(run, c)
                 for c in ("hmq_admit_bursts", "hmq_release_bursts"))
    compiles = sum(stats.delta(run, c)
                   for c in ("admission_compiles", "release_compiles"))
    return compiles / bursts if bursts else None
