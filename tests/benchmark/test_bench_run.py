"""The chip benchmark's run on the CPU: it refuses to measure without a
TPU, and with the chip check skipped, at a tiny size, its comparison with
the plain reference passes a sound run and fails a broken one and the
reference's int8 control."""
import itertools
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import discover, peaks, run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = BENCH["workloads"][0]["name"]
RUN = ["--workload", CELL, "--seed", str(2**33 + 1), "--seconds", "2",
       "--trace", "0"]

# the reduced qwen2.5-3b preset, served as the benchmark serves a cell
TINY = {"arch": "qwen2.5-3b", "model_type": "qwen2",
        "num_hidden_layers": 2, "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "torch_dtype": "float32",
        "engine": {"slots": 4, "max_slots": 4, "max_len": 64,
                   "chunk_tokens": 16, "seq_tile": 16, "page_tokens": 8,
                   "prefix_cache": True}}
TRAFFIC = {"generator": "open_loop", "arrivals": "poisson",
           "rate_per_s": 8.0, "warmup_burst": 2,
           "prompt_tokens": {"alpha": 1.2, "min": 8, "max": 24},
           "output_tokens": {"alpha": 1.2, "min": 8, "max": 32},
           "shared_headers": {"count": 2, "tokens": 8}, "check_tokens": 48}
# the committed cell's limits; float32 on both sides at this size, so a
# sound run's served tokens are the reference's best up to rounding, and
# a fault or the int8 control reads more
LIMITS = json.loads((ROOT / "benchmarks" / "chip" / "checks"
                     / f"{CELL}.json").read_text())["limits"]


def _cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)] + RUN, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_on_cpu_fails_naming_the_platform():
    got = _cli(ROOT, ROOT / BENCH["command"][1])
    assert got.returncode != 0
    assert "'cpu'" in got.stderr and "TPU" in got.stderr
    assert '"correct"' not in got.stdout


def test_run_fails_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = _cli(tmp_path, tmp_path / BENCH["command"][1])
    assert got.returncode != 0
    assert '"correct"' not in got.stdout


def off_chip(monkeypatch):
    """Let ``run.measure`` serve on the CPU: every registry entry at its
    reduced preset, a peaks row for the CPU, a short warm-up."""
    from repro.configs import registry
    monkeypatch.setattr(run, "WARMUP_S", 3.0)
    get = registry.get
    monkeypatch.setattr(registry, "get",
                        lambda arch, reduced=False: get(arch, reduced=True))
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        {"flops": 1e12, "hbm_bw": 1e11})


@pytest.fixture
def tiny(monkeypatch):
    """A run of the tiny cell with the chip check skipped."""
    off_chip(monkeypatch)
    bench = discover.load_benchmark()
    metrics = [(m, discover.load_module(
        discover.HERE / "metrics" / f"{m['name']}.py"))
        for m in bench["end_to_end"] if discover.reports(m, CELL)]
    cell = discover.Cell(name="tiny", chips=1, config=TINY,
                         arch=discover.arch("qwen2"), traffic=TRAFFIC,
                         check={"limits": LIMITS}, metrics=metrics)

    def go(seed=2**33 + 3):
        return run.measure(cell, seed=seed, seconds=4.0, trace=False,
                           devs=jax.devices()[:1])
    return go


def test_sound_run_is_correct(tiny):
    out = tiny()
    assert out["correct"], out["check"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"] and list(out)[-1] == "check"
    assert out["check"]["window_compiles"]["value"] == 0
    assert out["device"]["platform"] == "cpu"
    assert {"setup_s", "output_tokens_per_s"} <= set(out["metrics"])


def _break_step(monkeypatch, fault):
    """Break the timed path underneath the harness, through the engine's
    public ``step``: ``token`` alters one request's newest token where it
    is produced; ``swap`` exchanges two requests' newest tokens, as a
    batch whose rows are read back in the wrong order. Each step picks
    the next of the requests that gained a token, so that every slot's
    requests are hit: a fault kept to the first slot can miss every
    request that finishes in a window that a loaded host cuts to a few
    steps."""
    from repro.serve.engine import MultiPortEngine
    step = MultiPortEngine.step
    steps = itertools.count()

    def broken(self):
        seen = {id(r): len(r.generated) for r in self.slot_req if r}
        out = step(self)
        new = [r for r in self.slot_req
               if r and len(r.generated) > seen.get(id(r), 0)]
        i = next(steps)
        if fault == "token" and new:
            r = new[i % len(new)]
            r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab
        elif fault == "swap" and len(new) >= 2:
            a = new[i % len(new)].generated
            b = new[(i + 1) % len(new)].generated
            a[-1], b[-1] = b[-1], a[-1]
        return out
    monkeypatch.setattr(MultiPortEngine, "step", broken)


@pytest.mark.parametrize("fault", ["token", "swap"])
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, fault):
    _break_step(monkeypatch, fault)
    out = tiny()
    assert not out["correct"]
    assert not run.judge({k: v["value"] for k, v in out["check"].items()},
                         LIMITS)


def test_int8_control_departs_from_a_sound_run():
    """The reference computed in int8 (the next precision down) against
    the float32 reference at the tiny size, over the reference's own
    greedy tokens: the sound side reads no gap at all and keeps to the
    cell's limits, the control reads a gap on every seed. Whether the
    control fails the cell's limits is read on the chip, at the cell's
    size, through the same comparison (``calibrate.py``): at this size
    int8's error is too small to reach them."""
    import numpy as np

    from benchmarks.chip import weights
    from repro.configs import registry
    from repro.models import init_params
    qwen2 = discover.arch("qwen2")
    cfg = registry.get("qwen2.5-3b", reduced=True)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    w = discover.Widths.of(TINY, qwen2)
    for seed in (1, 2, 3):
        params = weights.make_weights(shapes, seed, tied=w.tied)
        rng = np.random.default_rng(seed)
        ids = np.zeros((8, 64), np.int32)
        ids[:, :24] = rng.integers(0, 256, (8, 24))
        lm = params["embed"]["w"].T
        for t in range(24, 56):          # the reference's greedy tokens
            h = qwen2.hidden(params, w, ids, int8=False)[:, t - 1]
            z = qwen2._norm(h, params["final_norm"]["scale"],
                            w.norm_eps) @ lm
            ids[:, t] = np.asarray(z.argmax(-1))
        seqs = [(row[:24].tolist(), row[24:56].tolist()) for row in ids]
        got = qwen2.check(params, w, seqs, 64, control=True)
        assert len(got["gaps"]) == len(got["control_gaps"]) == 256
        sound = run.gap_numbers(got["gaps"])
        control = run.gap_numbers(got["control_gaps"])
        assert sound["max_logit_gap"] < 1e-4 and run.judge(sound, LIMITS)
        assert control["max_logit_gap"] > 100 * max(
            sound["max_logit_gap"], 1e-6)
