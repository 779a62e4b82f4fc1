#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limit, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 [--rates 0.1 0.2 ...]

One process, so that every run after the first finds its programs
compiled. For each seed (and, with ``--rates``, each offered rate in
requests per second, overriding the traffic file's; given as many seeds
as rates, each rate runs on its own seed) it makes one run of the cell as
``run.py`` does, then runs the plain reference of the configuration's
architecture (``arch/<model_type>.py``) and its int8 control over the
same served tokens. Each run prints one JSON line: the rate, the seed,
the cell's end-to-end metrics, ``correct`` with the numbers compared,
the program's logit gaps (mean, widest, ...: the lower readings of the
limits), the control's (the upper readings) and whether the control
passes the cell's limits by the same comparison (it must not: the script
stops there if it does), and the admission queue's depth over the
window, whose growth marks a rate above the knee. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="*", default=None)
    args = ap.parse_args()

    from benchmarks.chip import discover, run
    cell = discover.load_cell(args.workload, traced=False)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        run.fail(f"needs a TPU, but JAX's first device is on platform "
                 f"{devs[0].platform!r}")
    run.compile_cache()
    rates = args.rates or [None]
    # as many seeds as rates: one seed to each rate; else every seed at each
    pairs = (list(zip(rates, args.seeds)) if len(args.seeds) == len(rates)
             else [(r, s) for r in rates for s in args.seeds])
    passed = []
    for rate, seed in pairs:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic))
        if rate is not None:
            c.traffic["rate_per_s"] = rate
        out = run.measure(c, seed=seed, seconds=args.seconds,
                          trace=False, devs=devs[:cell.chips],
                          calibrate=True)
        q = [d for _, d in out.pop("queue_depth")]
        k = max(1, len(q) // 4)
        print(json.dumps({
            "cell": cell.name, "rate": c.traffic.get("rate_per_s"),
            "seed": seed, "metrics": {n: m["value"] for n, m in
                                      out["metrics"].items()},
            "attempted": out["attempted"], "failed": out["failed"],
            "correct": out["correct"], "check": out["check"],
            "gaps": out["gaps"], "control_gaps": out["control_gaps"],
            "control_correct": out["control_correct"],
            "queue_first_quarter": sum(q[:k]) / k,
            "queue_last_quarter": sum(q[-k:]) / k,
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
        if out["control_correct"]:
            passed.append(seed)
    if passed:
        raise SystemExit(f"calibrate: the control kept to the cell's limits "
                         f"on seeds {passed}")


if __name__ == "__main__":
    main()
