"""1 minus the union of device operations over the traced window, in %."""
from bench import stats


def read(run):
    share = stats.idle_share(run)
    return None if share is None else 100.0 * share
