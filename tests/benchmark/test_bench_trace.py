"""Trace reduction: device busy and idle, per-op device time and idle gaps
labelled by the host span, on hand-made events and on a small trace
recorded on a TPU v5e."""
import gzip
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import driver, trace_reduce  # noqa: E402

RECORDED = pathlib.Path(__file__).parent / "data" / "v5e_qwen2-0.5b.xplane.pb.gz"


def test_reduce_by_hand():
    ms = 1_000_000
    ops = [("fusion.1", 0, 10 * ms), ("pool_step", 5 * ms, 20 * ms),
           ("fusion.1", 30 * ms, 40 * ms), ("fusion.1", 45 * ms, 70 * ms)]
    spans = [("bench.step", 0, 32 * ms), ("bench.idle", 32 * ms, 50 * ms)]
    s = trace_reduce.reduce([ops], spans)
    assert s.window_s == pytest.approx(0.050)
    assert s.busy_s == pytest.approx(0.035)          # [0,20] [30,40] [45,50]
    assert s.op_seconds["fusion.1"] == pytest.approx(0.025)
    assert s.kernel_seconds(("pool",)) == pytest.approx(0.015)
    assert s.gaps == [("bench.step", pytest.approx(0.010)),
                      ("bench.idle", pytest.approx(0.005))]
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert len(b["idle_gaps"]) == 2


def test_two_devices_average():
    ops_a = [("x", 0, 10)]
    ops_b = [("x", 0, 20)]
    s = trace_reduce.reduce([ops_a, ops_b], [("bench.step", 0, 20)])
    assert s.busy_s == pytest.approx(15e-9)
    assert s.op_seconds["x"] == pytest.approx(15e-9)


def test_union():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == \
        [(0, 3), (5, 9)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(RECORDED) as f, open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return out


def test_recorded_trace(recorded):
    """0.6 s of qwen2-0.5b serving rag-shared traffic on one TPU v5e."""
    from benchmarks.chip.work import decode_attn, pool_step, prefill_chunk
    devices, spans = trace_reduce.load_xplane(recorded, driver.SPANS)
    assert len(devices) == 1 and devices[0]
    assert {n for n, _, _ in spans} >= {driver.SPAN_STEP}
    s = trace_reduce.reduce(devices, spans)
    assert 0 < s.busy_s < s.window_s
    for work in (decode_attn, pool_step, prefill_chunk):
        assert s.kernel_seconds(work.MATCH) > 0, work.__name__
    assert sum(s.op_seconds.values()) >= s.busy_s
    labels = {g for g, _ in s.gaps}
    assert labels <= set(driver.SPANS) | {"none"}
    assert sum(d for _, d in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
