"""Device: share of the traced span with no operation running on the
chip, in percent (device trace)."""


def read(run):
    return run.idle_share()
