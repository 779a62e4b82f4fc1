"""Output tokens seen in the window over the window's seconds (host
clock)."""
from benchmarks.chip import stats


def read(run):
    return stats.rate(run.token_times(), run.t_open, run.t_close)
