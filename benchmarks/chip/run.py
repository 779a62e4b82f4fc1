#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process holds.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The run checks that JAX holds a TPU (and as many chips as the cell asks
for), else exits non-zero naming the platform. It keeps JAX's persistent
compilation cache where ``repro.launch.serve.enable_compile_cache`` puts
it (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is
set), with every program cached, makes the weights from ``--seed`` on the
device, and builds ``MultiPortEngine`` from the cell's configuration file.

Set-up, counted in ``setup_s``, then compiles every program the window
can run (``warm.py``): the pool's programs at every shape, rounds of
requests that run the prefill program at every batch bucket, and
``WARMUP_S`` of the cell's own traffic, which leaves the engine in its
steady state. The window drives the cell's traffic on the wall clock for
``--seconds``. With ``--trace 1`` the first ``TRACE_S`` seconds of the
window are traced by the JAX profiler and the per-layer metrics are
reported; otherwise the end-to-end ones. Cells whose metrics need every
request of the window to have its first token keep serving, without new
arrivals, for up to ``DRAIN_S`` after the window closes.

After the window the engine is freed and the plain reference of the
configuration's architecture (``arch/<model_type>.py``, its ``check``)
runs over a sample of the finished requests, drawn from the seed with
the one that served most tokens in it. The run is ``correct`` when the
sampled tokens' logit gaps keep to the cell's limits
(``checks/<cell>.json``), every finished request served exactly the
tokens it asked for, and no program was compiled inside the window (a
window that compiles measures the compiler).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check`` (each compared number with its limit,
also the last lines of standard error).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_S = 8.0       # traced span at the start of the window
DRAIN_S = 60.0      # longest wait for first tokens after the window
WARMUP_S = 30.0     # the cell's own traffic before the window opens
BURST_MAX_S = 600.0  # longest wait for the prefill rounds (they compile)


def note(msg: str) -> None:
    """A progress line on standard error, stamped with the seconds since
    the process started."""
    print(f"run: {time.perf_counter() - T_START:7.1f}s {msg}",
          file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    print(f"run: {msg}", file=sys.stderr)
    raise SystemExit(2)


def program_config(cell):
    """The program's registry entry for the configuration's ``arch``,
    with its ``norm_eps`` (an option the entry leaves at its default) set
    from the file. It must then be the configuration the file states, as
    the architecture's module reads it (``program``), in the file's
    dtype; a run of anything else measures another model."""
    import dataclasses

    from repro.configs import registry
    conf, dtype = cell.config, cell.config["torch_dtype"]
    try:
        want = dict(cell.arch.program(conf), param_dtype=dtype,
                    compute_dtype=dtype)
    except ValueError as e:
        fail(f"configuration file of {conf['arch']}: {e}")
    cfg = dataclasses.replace(registry.get(conf["arch"]),
                              norm_eps=cell.widths.norm_eps)
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        fail(f"registry {cfg.arch_id} departs from the configuration file "
             f"(program, file): {bad}")
    return cfg


def judge(got: dict, limits: dict) -> bool:
    """Whether every compared number is present and within its limit."""
    return all(got.get(k) is not None and got[k] <= v
               for k, v in limits.items())


def gap_numbers(gaps: list) -> dict:
    """The numbers a limit can hold a run's logit gaps to."""
    return {"mean_logit_gap": sum(gaps) / len(gaps) if gaps else None,
            "max_logit_gap": max(gaps) if gaps else None}


def gap_stats(gaps: list) -> dict:
    """Mean, widest, 99th percentile and share above zero of a run's
    logit gaps (for calibration)."""
    import numpy as np
    if not gaps:
        return {}
    g = np.asarray(gaps)
    return {"n": int(g.size), "mean": float(g.mean()), "max": float(g.max()),
            "p99": float(np.percentile(g, 99)),
            "nonzero": float((g > 0).mean())}


def sample(tracked: list, seed: int, want_tokens: int) -> list:
    """The finished requests to check: the one that served most tokens,
    then others in an order drawn from the seed, until ``want_tokens``
    served tokens are in."""
    import numpy as np
    if not tracked:
        return []
    order = sorted(tracked, key=lambda t: -len(t.req.generated))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng([seed, 7])
    out, n = [first], len(first.req.generated)
    for i in rng.permutation(len(rest)):
        if n >= want_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].req.generated)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmarks.chip import discover
    cell = discover.load_cell(args.workload, traced=bool(args.trace))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is on platform "
             f"{devs[0].platform!r}")
    if len(devs) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, JAX sees "
             f"{len(devs)}")
    compile_cache()
    out = measure(cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devs=devs[:cell.chips])
    for k, v in out["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out))


def compile_cache() -> None:
    """The program's persistent compilation cache, with every program in
    it: the engine's many small programs compile in well under the 1 s
    that JAX's default asks before it stores one."""
    import jax

    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(cell, *, seed: int, seconds: float, trace: bool, devs: list,
            calibrate: bool = False) -> dict:
    """Set up, warm up, measure, check; returns the result line's object.
    ``calibrate`` (for ``calibrate.py``) also runs the reference's int8
    control over the same served tokens and adds both sides' gap
    statistics, and the admission queue's depth after each step of the
    window."""
    import jax

    from benchmarks.chip import discover, driver, peaks, record, stats
    from benchmarks.chip import trace_reduce, warm, weights
    from repro.models import init_params
    from repro.serve.engine import MultiPortEngine

    meter = driver.CompileMeter()
    conf, w, traffic = cell.config, cell.widths, cell.traffic
    pk = peaks.peaks(devs[0].device_kind)
    cfg = program_config(cell)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    params = weights.make_weights(shapes, seed, tied=w.tied)
    ec = conf["engine"]
    eng = MultiPortEngine(params, cfg, **ec)
    arrivals = discover.generator(traffic).generate(
        traffic, seed=seed, seconds=seconds, vocab=w.vocab,
        warmup_s=WARMUP_S)

    # set-up: every program the window can run (warm.py), then the cell's
    # own traffic for WARMUP_S; then the window's arrivals, due from its
    # opening
    t_shapes = time.perf_counter()
    note("engine built; compiling the pool's shapes")
    built = warm.pool_shapes(
        eng.pool, max_slots=ec["max_slots"], max_len=ec["max_len"],
        min_read=min(ec["chunk_tokens"], traffic["prompt_tokens"]["min"]),
        prefix_cache=ec["prefix_cache"], log=note)
    t_burst = time.perf_counter()
    note(f"pool shapes done: {built}; prefill rounds")
    drv = driver.Driver(eng, [], t_origin=t_burst, chunk=ec["chunk_tokens"],
                        page_tokens=ec["page_tokens"])
    for batch in warm.prefill_rounds(max_slots=ec["max_slots"],
                                     chunk=ec["chunk_tokens"], vocab=w.vocab,
                                     seed=seed):
        drv.schedule(batch, origin=time.perf_counter())
        drv.run_until(t_burst + BURST_MAX_S,
                      stop=lambda: not drv.pending and not drv.live)
    t_origin = time.perf_counter()
    note("prefill rounds done; the cell's traffic")
    drv.schedule([a for a in arrivals if a.phase != "window"],
                 origin=t_origin)
    drv.run_until(t_origin + WARMUP_S)
    note("window opens")
    t_open = time.perf_counter()
    t_close = t_open + seconds
    drv.schedule([a for a in arrivals if a.phase == "window"], origin=t_open)
    setup_s = t_open - T_START
    attached0 = eng.prefix_stats["attached_tokens"]
    summary, tr0, tr1 = None, 0.0, 0.0
    if trace:
        trace_dir = ROOT / ".bench_trace" / f"{cell.name}.{seed}"
        drv.flush()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        tr0 = time.perf_counter()
        drv.run_until(min(t_close, tr0 + TRACE_S))
        drv.flush()
        tr1 = time.perf_counter()
        jax.profiler.stop_trace()
    drv.run_until(t_close)
    attached = eng.prefix_stats["attached_tokens"] - attached0

    def due_in_window():
        return [t for t in drv.tracked if t_open <= t.due < t_close]
    if any(getattr(m, "NEEDS_DRAIN", False) for _, m in cell.metrics):
        drv.run_until(t_close + DRAIN_S, stop=lambda: not drv.pending
                      and all(t.tokens for t in due_in_window()))
    drained = time.perf_counter()
    due = due_in_window()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)

    if trace:
        try:
            summary = trace_reduce.reduce_dir(trace_dir, driver.SPANS)
        except ValueError as e:      # the line then lacks the device metrics
            print(f"run: trace not reduced: {e}", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = record.RunRecord(
        setup_s=setup_s, t_open=t_open, t_close=t_close,
        drained_until=drained, tracked=drv.tracked, steps=drv.steps,
        widths=w, peaks=pk, trace=summary, trace_t0=tr0, trace_t1=tr1,
        prefix_attached=attached)
    metrics = {}
    for entry, mod in cell.metrics:
        v = mod.read(rec)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}

    # what the window did, for the record (earlier lines)
    backlog = traffic["arrivals"] == "backlog"
    held = [t for t in drv.tracked if t.admitted is not None
            and t.admitted <= t_close
            and (not t.tokens or t.tokens[-1] >= t_open)]
    attempted = held if backlog else due
    failed = sum(1 for t in attempted if t.req.shed_reason is not None
                 or (not backlog and not t.tokens))
    late = [t.submitted - t.due for t in due]
    stage_s = MultiPortEngine.final_stage_ladder(ec["max_len"],
                                                 ec["seq_tile"])[-1]
    staged = 2 * w.layers * ec["max_slots"] * stage_s * w.kv_heads \
        * w.head_dim * 4
    window_compiles = meter.between(t_open, t_close, meter.compiled)

    def programs(t0, t1):
        return (f"{meter.between(t0, t1, meter.compiled)} compiled, "
                f"{meter.between(t0, t1)} compiled or read back "
                f"({meter.seconds_between(t0, t1):.2f}s)")
    print(f"programs in the {seconds}s window: {programs(t_open, t_close)}")
    print(f"set-up {setup_s:.1f}s: to the engine {t_shapes - T_START:.1f}s "
          f"({programs(T_START, t_shapes)}); pool shapes "
          f"{t_burst - t_shapes:.1f}s ({programs(t_shapes, t_burst)}; "
          f"{built}); prefill rounds {t_origin - t_burst:.1f}s "
          f"({programs(t_burst, t_origin)}); traffic "
          f"{t_open - t_origin:.1f}s ({programs(t_origin, t_open)})")
    print(f"requests due in window {len(due)}, admitted "
          f"{sum(1 for t in due if t.admitted is not None)}, finished "
          f"{sum(1 for t in due if t.req.done)}, shed "
          f"{sum(1 for t in due if t.req.shed_reason is not None)}; "
          f"macro-cycles in window {len(rec.window_steps())}, flushes "
          f"{drv.flushes}")
    print(f"generator lateness (submit - due) p50 "
          f"{stats.percentile(late, 50)} s, max "
          f"{max(late) if late else None} s")
    print(f"peak_bytes_in_use {mem}; staged decode K+V per macro-cycle "
          f"{staged} bytes each way (computed, f32 staging)")

    # the comparison with the plain reference, after the program is freed
    finished = [t for t in drv.tracked if t.req.done and t.tokens
                and t.tokens[-1] >= t_open]
    short = sum(1 for t in finished
                if len(t.req.generated) != t.req.max_new)
    picked = sample(finished, seed, int(traffic["check_tokens"]))
    seqs = [(list(t.arrival.prompt), list(t.req.generated)) for t in picked]
    del eng, drv
    gc.collect()
    note("window closed; the reference")
    t_ref = time.perf_counter()
    ref = cell.arch.check(params, w, seqs, ec["max_len"],
                          control=calibrate) if seqs else {"gaps": []}
    gaps = ref["gaps"]
    got = gap_numbers(gaps)
    limits = {k: float(v) for k, v in cell.check["limits"].items()}
    correct = (judge(got, limits) and short == 0
               and window_compiles == 0)
    print(f"reference over {len(seqs)} requests, {len(gaps)} served tokens "
          f"in {time.perf_counter() - t_ref:.1f}s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if calibrate:
        out["gaps"] = gap_stats(gaps)
        out["control_gaps"] = gap_stats(ref.get("control_gaps", []))
        out["control_correct"] = judge(
            gap_numbers(ref.get("control_gaps", [])), limits)
        out["queue_depth"] = [(s.t1 - t_open, s.queue) for s in
                              rec.window_steps()]
    out["check"] = {k: {"value": got[k], "limit": v}
                    for k, v in limits.items()}
    out["check"]["short_answers"] = {"value": short, "limit": 0}
    out["check"]["window_compiles"] = {"value": window_compiles, "limit": 0}
    return out


if __name__ == "__main__":
    main()
