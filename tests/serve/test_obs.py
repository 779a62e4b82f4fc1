"""Host spans inside the engine (``repro.obs``): nothing is recorded
without a profiler session; under one, every ``step()`` records one
``engine.step`` whose children nest inside it and carry the bytes they
move."""
import pathlib

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import registry
from repro.models import init_params
from repro.serve import engine as engine_mod
from repro.serve.engine import MultiPortEngine

INNER = {"engine.retire", "engine.prefill", "engine.pool.issue",
         "engine.decode.stage"}
GATHER_PARENTS = {"engine.decode.stage", "engine.prefill",
                  "engine.pool.issue"}


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get("qwen2.5-3b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params):
    return MultiPortEngine(params, cfg, slots=4, max_slots=4, max_len=64,
                           chunk_tokens=16, seq_tile=16, page_tokens=8,
                           prefix_cache=True)


def _submit(eng, seed, n=6):
    """``n`` requests behind one shared 12-token header; the first is
    served until its prompt is in the prefix index, so the others attach
    the header (gathered back to the host) and copy its shared page on
    their first write."""
    rng = np.random.default_rng(seed)
    head = list(rng.integers(0, eng.cfg.vocab, 12))
    reqs = []
    for k in rng.integers(2, 20, n):
        reqs.append(eng.submit(head + list(rng.integers(0, eng.cfg.vocab,
                                                        k)), max_new=6))
        while len(reqs) == 1 and not reqs[0].generated:
            eng.step()
    return reqs


def test_no_profiler_session_records_nothing(setup):
    cfg, params = setup
    obs.clear()
    eng = _engine(cfg, params)
    _submit(eng, 1)
    eng.run()
    assert eng.prefix_stats["hits"] > 0
    assert obs.recorded(-np.inf, np.inf) == []
    assert obs.span("a") is obs.span("b", rows=1)
    with obs.span("a") as counts:
        counts["h2d_bytes"] = 1            # a body only assigns
    assert obs.recorded(-np.inf, np.inf) == []


def _children(spans, parent):
    return [s for s in spans if s.parent == parent.name
            and parent.t0 <= s.t0 and s.t1 <= parent.t1]


def test_traced_steps_nest_and_count(setup, tmp_path):
    cfg, params = setup
    eng = _engine(cfg, params)
    _submit(eng, 2)
    eng.step()                              # compile outside the trace
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        n = 0
        while eng.pending_work() and n < 12:
            eng.step()
            n += 1
    finally:
        jax.profiler.stop_trace()
    spans = obs.recorded(-np.inf, np.inf)
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == n and all(s.parent is None for s in steps)
    first = steps[0].counts["cycle"]
    assert [s.counts["cycle"] for s in steps] == list(range(first,
                                                            first + n))
    names = {s.name for s in spans}
    assert INNER | {"engine.pool.gather"} <= names
    for s in spans:
        if s.name in INNER:
            assert s.parent == "engine.step", s
        elif s.name == "engine.pool.gather":
            assert s.parent in GATHER_PARENTS, s
            assert s.counts["d2h_bytes"] > 0
    # every span lies inside a step; direct children are disjoint and
    # inside their parent, so the self times add up to each step
    for st in steps:
        inside = [s for s in spans if st.t0 <= s.t0 and s.t1 <= st.t1
                  and s is not st]
        total = 0.0
        for p in [st] + inside:
            kids = sorted(_children(inside, p), key=lambda s: s.t0)
            for a, b in zip(kids, kids[1:]):
                assert a.t1 <= b.t0
            total += (p.t1 - p.t0) - sum(k.t1 - k.t0 for k in kids)
        assert total == pytest.approx(st.t1 - st.t0, rel=1e-9)
    assert len(spans) == len([s for st in steps for s in spans
                              if st.t0 <= s.t0 and s.t1 <= st.t1])

    # the decode staging uploads both staged caches at f32, plus the
    # lengths and the last tokens (one int32 per row each)
    nb = 4
    stage_s = eng._stage_buckets[-1]
    cache = 2 * cfg.n_layers * nb * stage_s * cfg.n_kv_heads \
        * cfg.head_dim_ * 4
    stages = [s for s in spans if s.name == "engine.decode.stage"]
    assert stages
    for s in stages:
        assert s.counts["h2d_bytes"] == cache + 2 * nb * 4
        assert 1 <= s.counts["rows"] <= nb
    # retire reads back one KV word per row, a word being
    # 2 * n_layers * n_kv_heads * head_dim_ f32 values, and one int32
    # token per row
    word_bytes = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim_ * 4
    for s in (s for s in spans if s.name == "engine.retire"):
        assert s.counts["d2h_bytes"] == nb * word_bytes + nb * 4
    for s in (s for s in spans if s.name == "engine.pool.issue"):
        assert s.counts["lanes"] > 0 and s.counts["h2d_bytes"] > 0

    # the same spans, with their counts, are in the profiler's trace
    pb = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(pb))
    host = [e for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]
    assert sum(e.name == "engine.step" for e in host) == n
    got = {e.name: dict(e.stats) for e in host}
    assert "d2h_bytes" in got["engine.retire"]


def test_recorded_keeps_the_window(tmp_path):
    obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer", rows=2) as c:
            with obs.span("inner"):
                pass
            c["h2d_bytes"] = 8
    finally:
        jax.profiler.stop_trace()
    inner, outer = obs.recorded(-np.inf, np.inf)
    assert (inner.name, inner.parent) == ("inner", "outer")
    assert outer.parent is None
    assert outer.counts == {"rows": 2, "h2d_bytes": 8}
    assert obs.recorded(outer.t0, outer.t1) == [inner, outer]
    assert obs.recorded(inner.t0, inner.t1) == [inner]
    assert obs.recorded(outer.t1, np.inf) == []
    obs.clear()
    assert obs.recorded(-np.inf, np.inf) == []


def test_admit_stamp_between_submit_and_first_token(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    reqs = _submit(eng, 3)
    eng.run()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_finish


def test_per_cycle_logs_are_bounded(setup, monkeypatch):
    cfg, params = setup
    monkeypatch.setattr(engine_mod, "LOG_CYCLES", 4)
    eng = _engine(cfg, params)
    _submit(eng, 4)
    eng.run()
    assert eng.cycles > 4
    assert len(eng.port_log) == len(eng.schedule_log) == 4
