"""The one traffic generator: open-loop arrivals on the wall clock.

A traffic file (``benchmarks/chip/traffic/<mix>.json``) names this
generator and gives its parameters; ``generate`` turns them and a seed
into the list of requests one run offers, with due times in seconds from
the start of warm-up. The bounded-Pareto lengths, the exponential
(Poisson) gaps and the shared-header overlay follow
``repro.serve.traffic``, re-expressed in seconds and kept here so that a
change to the program cannot move the yardstick.

Every seed offers the same work. Lengths and gaps are stratified draws:
the bounded-Pareto and exponential quantiles at ``(i + 0.5) / n`` for
``i < n``, so each phase holds the same multiset of prompt lengths,
output lengths and gaps whatever the seed. The seed permutes their order
and draws the token ids and the headers' contents. Runs with different
seeds then differ by arrangement, not by the amount of work.

Warm-up requests (phases ``burst`` and ``warmup``) are due in seconds
from the start of the traffic's warm-up, over the ``warmup_s`` the
harness gives. Window requests (phase ``window``) are due in seconds from
the window's opening, over the window.

Parameters (all lengths in tokens, times in seconds):

``arrivals``        ``"poisson"`` (``rate_per_s`` requests per second in
                    warm-up and window alike) or ``"backlog"`` (``backlog``
                    requests all due at the start of warm-up).
``warmup_burst``    requests due at time 0 of warm-up on top of the
                    Poisson stream, lengths from the mix, so that the
                    engine starts near its steady occupancy: about the
                    rate times a request's mean time in the engine.
``prompt_tokens``   ``{"alpha", "min", "max"}`` bounded Pareto.
``output_tokens``   same, for ``max_new``.
``shared_headers``  ``null`` or ``{"count", "tokens"}``: each prompt's
                    first ``min(tokens, len - 1)`` tokens are replaced by
                    one of ``count`` headers, assigned round robin in
                    arrival order. Lengths and due times do not change.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float                 # seconds after warm-up starts
    prompt: tuple
    max_new: int
    phase: str                   # "burst", "warmup" or "window"


def bounded_pareto_quantiles(alpha: float, lo: float, hi: float,
                             n: int) -> np.ndarray:
    """Integer lengths at the bounded-Pareto(alpha) quantiles
    ``(i + 0.5) / n`` on ``[lo, hi]`` (the inverse CDF of
    ``repro.serve.traffic._bounded_pareto``), sorted ascending."""
    u = (np.arange(n) + 0.5) / n
    ratio = (lo / hi) ** alpha
    x = lo / (1.0 - u * (1.0 - ratio)) ** (1.0 / alpha)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The exponential quantiles ``(i + 0.5) / n`` of a Poisson process at
    ``rate`` per second: ``n`` gaps whose mean is close to ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _phase(rng, params: dict, n: int, start: float, span: float,
           phase: str) -> list:
    """``n`` requests spread over ``[start, start + span)``: stratified
    lengths and gaps, permuted by ``rng``."""
    if n <= 0:
        return []
    p, o = params["prompt_tokens"], params["output_tokens"]
    plen = rng.permutation(bounded_pareto_quantiles(p["alpha"], p["min"],
                                                    p["max"], n))
    olen = rng.permutation(bounded_pareto_quantiles(o["alpha"], o["min"],
                                                    o["max"], n))
    if params["arrivals"] == "backlog":
        due = np.full(n, start)
    else:
        rate = float(params["rate_per_s"])
        c = np.cumsum(rng.permutation(exponential_gaps(rate, n)))
        # the gaps' sum spans the phase with one mean gap of room at the end
        due = start + c * span / (c[-1] + 1.0 / rate)
    return [(float(due[i]), int(plen[i]), int(olen[i]), phase)
            for i in range(n)]


def generate(params: dict, *, seed: int, seconds: float, vocab: int,
             warmup_s: float) -> list[Arrival]:
    """The requests one run offers: warm-up ones (over ``warmup_s``) by
    due time, then the window's by due time."""
    if params["arrivals"] not in ("poisson", "backlog"):
        raise ValueError(f"unknown arrivals {params['arrivals']!r}")
    rng = np.random.default_rng([seed, zlib.crc32(b"open_loop")])
    warm = float(warmup_s)
    rows = []
    rows += _phase(rng, dict(params, arrivals="backlog"),
                   int(params.get("warmup_burst", 0)), 0.0, 0.0, "burst")
    if params["arrivals"] == "backlog":
        rows += _phase(rng, params, int(params["backlog"]), 0.0, warm,
                       "warmup")
    else:
        rate = float(params["rate_per_s"])
        rows += _phase(rng, params, round(rate * warm), 0.0, warm, "warmup")
        rows += _phase(rng, params, round(rate * seconds), 0.0, seconds,
                       "window")
    rows.sort(key=lambda r: (r[3] == "window", r[0]))
    # token ids: one stream per run, drawn in arrival order
    tok = np.random.default_rng([seed, zlib.crc32(b"tokens")])
    prompts = [tuple(int(t) for t in tok.integers(0, vocab, n))
               for _, n, _, _ in rows]
    hdr = params.get("shared_headers")
    if hdr:
        hrng = np.random.default_rng([seed, zlib.crc32(b"headers")])
        heads = [tuple(int(t) for t in row) for row in
                 hrng.integers(0, vocab, (int(hdr["count"]),
                                          int(hdr["tokens"])))]
        out = []
        for i, pr in enumerate(prompts):
            head = heads[i % len(heads)]
            k = min(len(head), len(pr) - 1)   # the last token stays private
            out.append(head[:k] + pr[k:])
        prompts = out
    return [Arrival(due_s=r[0], prompt=pr, max_new=r[2], phase=r[3])
            for r, pr in zip(rows, prompts)]

