"""From a profiler trace to device busy and idle time, per-kernel device
time, and the longest idle gaps labelled by what the host was doing.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes, with
nothing but ``jax.profiler.ProfileData``: the device ops of each TPU (the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the harness's own
host spans (``bench.step`` and the rest, ``TraceAnnotation`` events on the
host plane). Both are on the trace's one clock. ``reduce`` does the rest
on plain lists, so it is tested on a small recorded trace.

- The window is the first host span's start to the last one's end.
- Busy time is the union of a device's op intervals inside the window,
  averaged over the devices; idle is the window less busy.
- Per-op device time sums each op's event durations inside the window,
  averaged over the devices, by the op's HLO instruction name without
  its numeric suffix (an event's name is the instruction's whole text,
  ``%fused_decode_attention.6 = (...) custom-call(...)``; a Pallas
  kernel's instruction is named after the function that called it). A
  kernel's time sums the ops whose name holds one of its ``MATCH``
  substrings. Ops nest (a ``while`` holds its body's ops), so per-op
  times may sum to more than the busy time.
- An idle gap is a stretch of the window in which a device runs no op; its
  label is the host span that overlaps it most ("none" when no span does).
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    op_seconds: dict                 # op name -> device seconds
    gaps: list                       # [(label, seconds)], longest first

    def kernel_seconds(self, match) -> float:
        return sum(s for n, s in self.op_seconds.items()
                   if any(m in n for m in match))

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def op_name(event: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion``."""
    return re.sub(r"\.\d+$", "", event.split(" = ", 1)[0].lstrip("%"))


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _label(gap, spans) -> str:
    a, b = gap
    best, got = "none", 0.0
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > got:
            best, got = name, ov
    return best


def reduce(devices: list, spans: list) -> TraceSummary:
    """``devices``: one list of ``(op name, start_ns, end_ns)`` per device;
    ``spans``: host ``(name, start_ns, end_ns)``."""
    if not spans or not any(devices):
        raise ValueError(f"a trace needs host spans and device ops: "
                         f"{len(spans)} spans, ops per device "
                         f"{[len(d) for d in devices]}")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy = 0.0
    per_op: collections.Counter = collections.Counter()
    gaps = []
    for ops in devices:
        ivs = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy += sum(b - a for a, b in ivs)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] += d
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a, b), spans), (b - a) / 1e9))
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(busy_s=busy / n / 1e9, window_s=(hi - lo) / 1e9,
                        op_seconds={k: v / n / 1e9 for k, v in per_op.items()},
                        gaps=gaps)


def load_xplane(path, span_names) -> tuple[list, list]:
    """(device ops per TPU, host spans named in ``span_names``)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = [(op_name(e.name), e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns)
                      for line in plane.lines for e in line.events
                      if e.name in span_names]
    return devices, spans


def reduce_dir(trace_dir, span_names) -> TraceSummary:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(*load_xplane(files[-1], span_names))
