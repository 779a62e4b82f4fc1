"""Decode staging: self time of ``engine.decode.stage`` per step, in ms —
filling the decode staging caches, their upload and the decode
program's dispatch (host clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, "engine.decode.stage")
