"""Host spans inside the engine, recorded only while a JAX profiler
session is active.

``span(name, **counts)`` is a context manager. With no profiler session
(``jax.profiler.TraceAnnotation.is_enabled()`` is False) it returns one
shared no-op: a single check, nothing allocated. With one, it opens a
``TraceAnnotation`` — the span lands on the device trace's clock in the
profiler's ``.xplane.pb`` — and, on exit, appends a :class:`Span` to a
bounded in-memory buffer on the ``time.perf_counter`` clock, its parent
taken from a per-thread stack of open spans.

Counts ride on spans: the body may assign to the dict the ``with``
yields (``c["h2d_bytes"] = x.nbytes``); they are the annotation's
metadata and the recorded span's ``counts``. The no-op yields one shared
dict that nothing reads, so a body only assigns to it.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

MAX_SPANS = 1 << 17


class Span(NamedTuple):
    name: str
    t0: float                 # perf_counter seconds
    t1: float
    parent: str | None        # the enclosing span's name, None at the top
    counts: dict


_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_open = threading.local()


class _Noop:
    counts: dict = {}

    def __enter__(self) -> dict:
        return self.counts

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _Noop()


class _Recording:
    __slots__ = ("name", "counts", "t0", "parent", "annotation", "stack")

    def __init__(self, name: str, counts: dict):
        self.name, self.counts = name, counts

    def __enter__(self) -> dict:
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.stack = stack
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self.counts

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self.counts:
            self.annotation.set_metadata(**self.counts)
        self.annotation.__exit__(*exc)
        self.stack.pop()
        _spans.append(Span(self.name, self.t0, t1, self.parent, self.counts))


def span(name: str, **counts):
    """A host span called ``name``, recorded while a profiler session is
    active; ``counts`` start its counts."""
    if not TraceAnnotation.is_enabled():
        return _NOOP
    return _Recording(name, counts)


def recorded(t0: float, t1: float) -> list:
    """The recorded spans that lie inside ``[t0, t1]`` (perf_counter
    seconds), in the order they closed."""
    return [s for s in _spans if t0 <= s.t0 and s.t1 <= t1]


def clear() -> None:
    _spans.clear()
