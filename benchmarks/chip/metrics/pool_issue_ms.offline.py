"""Pool traversal: self time of ``engine.pool.issue`` per step, in ms — the
port requests' lane arrays, their upload and the pool step's dispatch
(host clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, "engine.pool.issue")
