"""Model step: self time of ``engine.prefill`` per step, in ms — admission,
prefix attach, the chunk staging, its upload, the prefill program and its
results read back (host clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, "engine.prefill")
