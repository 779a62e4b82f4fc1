"""What one run measured, as the metric readers see it.

A reader (``metrics/<name>.py``) is a function ``read(run) -> float |
None`` of a ``RunRecord``. It returns None when the run holds nothing for
it to read — no trace, no requests of that kind, no kernel events — and
the harness then leaves the metric out of the line. A share of a roofline
or of a peak is never returned as 0 for want of data.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunRecord:
    setup_s: float
    t_open: float                # window, perf_counter seconds
    t_close: float
    drained_until: float         # end of the drain after the window
    tracked: list                # driver.Tracked, every request submitted
    steps: list                  # driver.StepWork, in order
    widths: object               # discover.Widths
    peaks: dict                  # peaks.PEAKS row
    trace: object = None         # trace_reduce.TraceSummary of the traced span
    trace_t0: float = 0.0        # traced span, perf_counter seconds
    trace_t1: float = 0.0
    prefix_attached: int = 0     # prefix-cache tokens attached in the window

    # ---- requests ---------------------------------------------------------
    def token_times(self) -> list:
        return [x for t in self.tracked for x in t.tokens]

    # ---- steps ------------------------------------------------------------
    def steps_between(self, t0: float, t1: float) -> list:
        return [s for s in self.steps if t0 <= s.t0 and s.t1 <= t1]

    def window_steps(self) -> list:
        return self.steps_between(self.t_open, self.t_close)

    def work(self, module, steps) -> tuple[float, float]:
        f = b = 0.0
        for s in steps:
            df, db = module.count(self.widths, s)
            f += df
            b += db
        return f, b

    # ---- device -----------------------------------------------------------
    def roofline(self, module) -> float | None:
        """Least time the traced span's work for ``module``'s kernel could
        take at the chip's peaks, over the kernel's summed device time in
        the trace, in percent."""
        if self.trace is None:
            return None
        t_kernel = self.trace.kernel_seconds(module.MATCH)
        flops, nbytes = self.work(module, self.steps_between(self.trace_t0,
                                                             self.trace_t1))
        if t_kernel <= 0 or (flops <= 0 and nbytes <= 0):
            return None
        t_min = max(flops / self.peaks["flops"],
                    nbytes / self.peaks["hbm_bw"])
        return 100.0 * t_min / t_kernel

    def mfu(self) -> float | None:
        """Model FLOPs of the window's tokens over the window's seconds at
        the chip's peak, in percent."""
        from benchmarks.chip.work import model_step
        flops, _ = self.work(model_step, self.window_steps())
        if flops <= 0:
            return None
        return 100.0 * flops / ((self.t_close - self.t_open)
                                * self.peaks["flops"])

    def idle_share(self) -> float | None:
        if self.trace is None or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)
