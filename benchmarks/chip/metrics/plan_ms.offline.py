"""Engine macro-cycle: self time of the engine's ``engine.step`` span per
step, in ms — deadline and overload checks, the FSM walk, footprints,
the scheduler's plan, bookkeeping, prefix registration (host clock)."""
from benchmarks.chip import spans


def read(run):
    return spans.self_ms(run, spans.STEP)
