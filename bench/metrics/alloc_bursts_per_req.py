"""Support-core bursts (admission, release and decode) per completed
request, over the window and its drain."""
from bench import stats


def read(run):
    bursts = sum(stats.delta(run, c) for c in (
        "hmq_admit_bursts", "hmq_release_bursts", "decode_bursts"))
    return bursts / max(stats.completed(run), 1)
