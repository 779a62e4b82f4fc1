"""Engine macro-cycle: bytes moved between host and device per step, in
10^6 bytes — the ``h2d_bytes`` and ``d2h_bytes`` of every span inside
``engine.step``."""
from benchmarks.chip import spans


def read(run):
    n = spans.count_per_step(run, spans.BYTES)
    return None if n is None else n / 1e6
