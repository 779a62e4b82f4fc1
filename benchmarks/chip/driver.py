"""The wall-clock open-loop driver and what it records.

The driver submits each request at its due time, calls
``MultiPortEngine.step()`` while the engine has work, ``flush()`` when only
an in-flight decode remains, and sleeps until the next due time when the
engine is idle. After every ``step()`` it reads the engine's requests —
``slot``, ``generated``, ``done`` — and the public counters
``prefix_stats`` and ``slot_len``, and records:

- when each request was first seen holding a slot, and when each of its
  tokens was first seen (the times a client streaming from this loop
  would see them);
- the work the macro-cycle did (``StepWork``): decode rows with their
  context lengths, prompt chunks with their offsets, pool words written,
  read and scrubbed, and output tokens, for the work counts in ``work/``.

Host spans: every ``step()``, ``flush()``, submit and idle sleep runs
inside a ``jax.profiler.TraceAnnotation`` (``bench.step``, ...), so that a
device trace can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import dataclasses
import time

import jax

SPAN_STEP, SPAN_FLUSH = "bench.step", "bench.flush"
SPAN_SUBMIT, SPAN_IDLE = "bench.submit", "bench.idle"
SPANS = (SPAN_STEP, SPAN_FLUSH, SPAN_SUBMIT, SPAN_IDLE)


@dataclasses.dataclass
class Tracked:
    """One submitted request as the driver saw it (perf_counter seconds)."""
    arrival: object              # generators' Arrival
    due: float
    submitted: float
    req: object                  # the engine's Request
    admitted: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    consumed: int = 0            # prompt tokens attached or computed
    attached: int = 0
    scrubbed: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.arrival.prompt)


@dataclasses.dataclass
class StepWork:
    """What one macro-cycle did, as far as the requests show it."""
    t0: float
    t1: float
    decode_rows: list = dataclasses.field(default_factory=list)  # ctx lens
    chunks: list = dataclasses.field(default_factory=list)  # (offset, n)
    words_written: int = 0
    words_read: int = 0
    words_scrubbed: int = 0
    out_tokens: int = 0
    queue: int = 0               # requests waiting for admission after it
    flush: bool = False          # a flush() of the in-flight decode alone


class CompileMeter:
    """Counts programs compiled or read back from the persistent cache,
    from JAX's own events: one ``backend_compile_duration`` per program,
    preceded by a ``cache_hits`` event when it was read back. ``times`` and
    ``durations`` hold every program; ``compiled`` the times of those that
    were compiled, not read back."""

    def __init__(self):
        self.times: list[float] = []
        self.durations: list[float] = []
        self.compiled: list[float] = []
        self._hit = False

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self._hit = True

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.perf_counter())
                self.durations.append(secs)
                if not self._hit:
                    self.compiled.append(self.times[-1])
                self._hit = False
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float, times=None) -> int:
        return sum(1 for t in (self.times if times is None else times)
                   if t0 <= t <= t1)

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(d for t, d in zip(self.times, self.durations)
                   if t0 <= t <= t1)


class Driver:
    def __init__(self, eng, arrivals, *, t_origin: float, chunk: int,
                 page_tokens: int):
        self.eng = eng
        self.pending = list(arrivals)       # sorted by due time
        self.t_origin = t_origin
        self.chunk = chunk
        self.page_tokens = page_tokens
        self.tracked: list[Tracked] = []
        self.live: list[Tracked] = []       # not yet seen finished
        self.steps: list[StepWork] = []
        self.flushes = 0

    def schedule(self, arrivals, *, origin: float) -> None:
        """Offer ``arrivals`` from now on, due ``origin + due_s``, in place
        of any not yet submitted."""
        self.pending = list(arrivals)
        self.t_origin = origin

    # ---- the loop -----------------------------------------------------------
    def run_until(self, t_end: float, *, submit: bool = True,
                  stop=None) -> None:
        """Drive until ``t_end`` (perf_counter), or until ``stop()`` holds."""
        eng = self.eng
        while True:
            now = time.perf_counter()
            if now >= t_end or (stop is not None and stop()):
                return
            if submit:
                self._submit_due(now)
            if eng.pending_work():
                self.step()
            elif eng.has_inflight:
                self.flush()
            else:
                nxt = (self.t_origin + self.pending[0].due_s
                       if submit and self.pending else t_end)
                with jax.profiler.TraceAnnotation(SPAN_IDLE):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))

    def _submit_due(self, now: float) -> None:
        while self.pending and self.t_origin + self.pending[0].due_s <= now:
            a = self.pending.pop(0)
            with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
                req = self.eng.submit(list(a.prompt), a.max_new)
            t = Tracked(arrival=a, due=self.t_origin + a.due_s,
                        submitted=time.perf_counter(), req=req)
            self.tracked.append(t)
            self.live.append(t)

    def step(self) -> None:
        eng = self.eng
        pfx = eng.prefix_stats
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            eng.step()
        t1 = time.perf_counter()
        work = StepWork(t0=t0, t1=t1)
        self._observe(t1, work, pfx)
        work.queue = len(eng.admission)
        self.steps.append(work)

    def flush(self) -> None:
        t0 = time.perf_counter()
        pfx = self.eng.prefix_stats
        with jax.profiler.TraceAnnotation(SPAN_FLUSH):
            self.eng.flush()
        t1 = time.perf_counter()
        work = StepWork(t0=t0, t1=t1, flush=True)
        self._observe(t1, work, pfx)
        self.steps.append(work)
        self.flushes += 1

    # ---- what a step did -----------------------------------------------------
    def _observe(self, now: float, work: StepWork, pfx_before: dict) -> None:
        eng, c = self.eng, self.chunk
        pfx = eng.prefix_stats
        attached_now = pfx["attached_tokens"] - pfx_before["attached_tokens"]
        work.words_written += pfx["cow_words"] - pfx_before["cow_words"]
        fresh_done = []
        still = []
        for t in self.live:
            r = t.req
            fresh = t.admitted is None and r.slot is not None
            had = len(t.tokens)
            g = len(r.generated)
            if fresh:
                t.admitted = now
            if (fresh or t.consumed) and had == 0:
                # prefilling during this step: one chunk from `consumed`
                p = t.prompt_len
                if fresh and g == 0:
                    # still prefilling: the slot holds attached + one chunk
                    t.attached = max(0, eng.slot_len[r.slot] - c)
                    t.consumed = t.attached
                if fresh and g > 0:
                    fresh_done.append(t)     # attached count settled below
                else:
                    n = min(c, p - t.consumed)
                    work.chunks.append((t.consumed, n))
                    t.consumed += n
            for k in range(had, g):
                t.tokens.append(now)
                work.out_tokens += 1
                if k >= 1:                   # a decoded token, not the first
                    ctx = t.prompt_len + k
                    work.decode_rows.append(ctx)
                    work.words_read += ctx - 1
                    work.words_written += 1
            if r.done or r.shed_reason is not None:
                if r.done and not t.scrubbed:
                    pt = self.page_tokens
                    own = -(-(t.prompt_len + g - 1) // pt) - t.attached // pt
                    work.words_scrubbed += max(0, own) * pt
                    t.scrubbed = True
                continue
            still.append(t)
        # requests admitted and completed in one step: their attached
        # tokens are what the step attached beyond the others' (split evenly
        # where several such requests share a step)
        if fresh_done:
            known = sum(t.attached for t in self.live
                        if t.admitted == now and t not in fresh_done)
            left = max(0, attached_now - known)
            for i, t in enumerate(fresh_done):
                share = left // len(fresh_done) + (
                    1 if i < left % len(fresh_done) else 0)
                t.attached = min(share, t.prompt_len - 1)
                n = t.prompt_len - t.attached
                work.chunks.append((t.attached, n))
                t.consumed = t.prompt_len
        for _, n in work.chunks:
            work.words_written += n
        self.live = still
