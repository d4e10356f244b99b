"""Blocking device-to-host reads per decode step: the engine's
``decode_host_reads`` over its ``decode_steps``, across the window and its
drain.  None where the program does not count its reads."""
from bench import stats


def read(run):
    if "decode_host_reads" not in run.stats1:
        return None
    steps = stats.delta(run, "decode_steps")
    return stats.delta(run, "decode_host_reads") / steps if steps else None
