"""Percentiles and rates over a measured window."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (linear interpolation between order
    statistics) of every value given; None when there is none."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def token_gaps(token_times, t_open: float, t_close: float) -> list:
    """Every gap between successive tokens of one request whose later
    token was seen inside ``[t_open, t_close]``."""
    return [b - a for a, b in zip(token_times, token_times[1:])
            if t_open <= b <= t_close]


def rate(times, t_open: float, t_close: float) -> float:
    """Events seen inside ``[t_open, t_close]`` per second of the window."""
    n = sum(1 for t in times if t_open <= t <= t_close)
    return n / (t_close - t_open)
