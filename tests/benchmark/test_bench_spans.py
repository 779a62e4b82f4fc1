"""The per-layer metrics read from the engine's own spans (``spans.py``
and its seven readers): by hand on a filled recorder, None on an empty
one or a program without spans, and all seven from one traced run on the
CPU."""
import json
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import discover, peaks, record, run, spans  # noqa: E402
from repro import obs  # noqa: E402
from repro.obs import Span  # noqa: E402
from test_bench_run import CELL, LIMITS, TINY, TRAFFIC  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("plan_ms.offline", "retire_ms.offline", "prefill_ms.offline",
       "pool_issue_ms.offline", "pool_gather_ms.offline",
       "decode_staging_ms.offline", "host_device_mb.offline")
PARTS = NEW[:-1]                  # the self times that make up a step


def _reader(name):
    return discover.load_module(discover.HERE / "metrics" / f"{name}.py")


def _run(t0=0.0, t1=100.0):
    return record.RunRecord(setup_s=1.0, t_open=t0, t_close=t1,
                            drained_until=t1, tracked=[], steps=[],
                            widths=None, peaks={}, trace_t0=t0, trace_t1=t1)


def _step(t, h2d=1000, d2h=3000):
    """One 10-second macro-cycle starting at ``t``, as the engine nests
    its spans, in the order they close."""
    return [
        Span("engine.retire", t + 0, t + 2, "engine.step",
             {"d2h_bytes": d2h}),
        Span("engine.pool.gather", t + 2.5, t + 3, "engine.prefill",
             {"d2h_bytes": 10}),
        Span("engine.prefill", t + 2, t + 4, "engine.step",
             {"rows": 1, "h2d_bytes": 100, "d2h_bytes": 200}),
        Span("engine.pool.issue", t + 4, t + 4.5, "engine.step",
             {"lanes": 8, "h2d_bytes": 50}),
        Span("engine.pool.issue", t + 4.5, t + 5, "engine.step",
             {"lanes": 8, "h2d_bytes": 50}),
        Span("engine.pool.gather", t + 5.5, t + 6, "engine.decode.stage",
             {"d2h_bytes": 20}),
        Span("engine.pool.gather", t + 6, t + 6.5, "engine.decode.stage",
             {"d2h_bytes": 20}),
        Span("engine.decode.stage", t + 5, t + 8, "engine.step",
             {"rows": 2, "h2d_bytes": h2d}),
        Span("engine.step", t, t + 10, None, {"cycle": 0}),
    ]


@pytest.fixture
def filled():
    obs.clear()
    obs._spans.extend(_step(10.0) + _step(20.0))
    # outside every step: a flush between steps; outside the window
    obs._spans.append(Span("engine.retire", 30.5, 31, None,
                           {"d2h_bytes": 10**9}))
    obs._spans.extend(_step(200.0))
    yield
    obs.clear()


def test_readers_by_hand(filled):
    got = {n: _reader(n).read(_run()) for n in NEW}
    assert got == pytest.approx({
        "plan_ms.offline": 1e3 * 2.0,          # 10 less 2+2+0.5+0.5+3
        "retire_ms.offline": 1e3 * 2.0,
        "prefill_ms.offline": 1e3 * 1.5,
        "pool_issue_ms.offline": 1e3 * 1.0,
        "pool_gather_ms.offline": 1e3 * 1.5,
        "decode_staging_ms.offline": 1e3 * 2.0,
        "host_device_mb.offline": (3000 + 10 + 300 + 100 + 40 + 1000) / 1e6,
    })
    assert sum(got[n] for n in PARTS) == pytest.approx(1e3 * 10.0)


def test_bytes_follow_the_counts(filled):
    obs.clear()
    obs._spans.extend(_step(10.0, h2d=2 * 10**6, d2h=4 * 10**6))
    got = _reader("host_device_mb.offline").read(_run())
    assert got == pytest.approx(6.0 + 450e-6)


def test_readers_give_none_without_spans(monkeypatch):
    obs.clear()
    for n in NEW:
        assert _reader(n).read(_run()) is None, n
    obs._spans.extend(_step(10.0))
    assert _reader("plan_ms.offline").read(_run(50.0, 60.0)) is None
    monkeypatch.setattr(spans, "obs", None)    # a program without repro.obs
    for n in NEW:
        assert _reader(n).read(_run()) is None, n
    obs.clear()


def test_entries_read_the_engine_spans():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for n in NEW:
        m = entries[n]
        assert (m["source"], m["moves"], m["better"]) == (
            "host_clock", "output_tokens_per_s", "lower")
        assert m["workloads"] == [CELL]


def test_traced_run_on_cpu_reports_all_seven(monkeypatch):
    """One tiny traced run with the chip check skipped: the device trace
    is not reduced on the CPU, but the engine's spans are recorded, and
    the step's parts add up to the steps' mean duration."""
    from repro.configs import registry
    monkeypatch.setattr(run, "WARMUP_S", 2.0)
    monkeypatch.setattr(run, "TRACE_S", 2.0)
    get = registry.get
    monkeypatch.setattr(registry, "get",
                        lambda arch, reduced=False: get(arch, reduced=True))
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        {"flops": 1e12, "hbm_bw": 1e11})
    bench = discover.load_benchmark()
    metrics = [(m, discover.load_module(
        discover.HERE / "metrics" / f"{m['name']}.py"))
        for m in bench["per_layer"] if m["name"] in NEW]
    cell = discover.Cell(name="tiny", chips=1, config=TINY,
                         arch=discover.arch("qwen2"), traffic=TRAFFIC,
                         check={"limits": LIMITS}, metrics=metrics)
    obs.clear()
    out = run.measure(cell, seed=2**33 + 5, seconds=3.0, trace=True,
                      devs=jax.devices()[:1])
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == set(NEW)
    assert all(v >= 0 for v in got.values()), got
    assert got["host_device_mb.offline"] > 0
    steps, _ = spans.in_steps(_run(-float("inf"), float("inf")))
    mean_ms = 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
    assert sum(got[n] for n in PARTS) == pytest.approx(mean_ms, rel=1e-6)
    obs.clear()
