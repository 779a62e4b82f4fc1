"""Engine macro-cycle: mean wall time of the driver's span around
``step()`` over the window's steps, in milliseconds (host clock)."""


def read(run):
    steps = [s for s in run.window_steps() if not s.flush]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
