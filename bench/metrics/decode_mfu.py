"""Model FLOPs of the traced decode steps (2 N per active lane plus
attention over the live KV) over their device time times the chip's bf16
peak, in %."""
from bench import stats


def read(run):
    mfu = stats.decode_mfu(run)
    return None if mfu is None else 100.0 * mfu
