"""Process start to window open: weights, engine, compile or cache load,
warm-up (host clock)."""


def read(run):
    return run.setup_s
