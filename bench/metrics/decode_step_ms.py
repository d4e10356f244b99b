"""Device time of the decode executable per run, from the trace."""
from bench import stats


def read(run):
    return stats.decode_step_ms(run)
