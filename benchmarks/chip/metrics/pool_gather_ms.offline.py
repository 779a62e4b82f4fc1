"""Pool traversal: time of ``engine.pool.gather`` per step, in ms — every
read of pool words to the host, each waiting on the pool step (host
clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, "engine.pool.gather")
