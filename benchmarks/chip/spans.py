"""Per-macro-cycle host time and byte counts from the engine's own spans.

The program records host spans (``repro.obs``) while the JAX profiler
traces, on the ``time.perf_counter`` clock: ``engine.step`` around every
``step()``, and inside it ``engine.retire``, ``engine.prefill``,
``engine.pool.issue``, ``engine.decode.stage`` and ``engine.pool.gather``,
each with its parent's name and counts (``h2d_bytes``, ``d2h_bytes``, ...).
The readers here take the spans of the traced span that lie inside an
``engine.step`` (a retire that a ``flush()`` between macro-cycles forces
is outside every macro-cycle, as it is outside ``cycle_ms.offline``) and
give per-step figures. A span's self time is its duration less its
children's, so the self times of ``engine.step`` and of the spans inside
it add up to the steps' duration.

A program without those spans (no ``repro.obs``) or a run that recorded
none gives None.
"""
from __future__ import annotations

import bisect

try:
    from repro import obs
except ImportError:          # a program that records no spans of its own
    obs = None

STEP = "engine.step"
BYTES = ("h2d_bytes", "d2h_bytes")


def in_steps(run) -> tuple[list, list]:
    """(the ``engine.step`` spans of the traced span, every recorded span
    that lies inside one of them, the steps included)."""
    if obs is None:
        return [], []
    spans = obs.recorded(run.trace_t0, run.trace_t1)
    steps = sorted((s for s in spans if s.name == STEP), key=lambda s: s.t0)
    starts = [s.t0 for s in steps]
    inside = []
    for s in spans:
        i = bisect.bisect_right(starts, s.t0) - 1
        if i >= 0 and s.t1 <= steps[i].t1:
            inside.append(s)
    return steps, inside


def self_ms(run, name: str) -> float | None:
    """Self time of the spans called ``name`` per macro-cycle, in ms."""
    steps, spans = in_steps(run)
    if not steps:
        return None
    own = sum(s.t1 - s.t0 for s in spans if s.name == name)
    kids = sum(s.t1 - s.t0 for s in spans if s.parent == name)
    return 1e3 * (own - kids) / len(steps)


def count_per_step(run, keys) -> float | None:
    """The sum of the counts ``keys`` over every span, per macro-cycle."""
    steps, spans = in_steps(run)
    if not steps:
        return None
    return sum(s.counts.get(k, 0) for s in spans for k in keys) / len(steps)
