"""95th percentile of every gap between consecutive output tokens of every
request due in the window."""
from bench import stats


def read(run):
    return stats.pct(stats.itl_ms(run), 95)
