"""Model step: self time of ``engine.retire`` per step, in ms — waiting on
the decode program and reading its results back (host clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, "engine.retire")
