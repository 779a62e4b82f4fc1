"""Serving launcher: the multi-port engine over a token-model architecture.

Serves the architecture's published configuration; ``--reduced`` swaps in
its small preset, the size CPU runs use (Pallas kernels interpreted there,
compiled on a TPU):

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --requests 8 --max-new 8 [--single-port]

Multi-device (data-parallel KV — the paged pool sharded page-aligned over a
``kv`` mesh axis, kernels shard_map'd by home device):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --reduced --kv-shards 4

Open-loop (requests ARRIVE on a virtual-clock schedule instead of all being
submitted up front — seeded Poisson via ``--arrival-rate``, or a JSONL
trace via ``--trace``; ``--slo`` prints p99-TTFT SLO attainment in
virtual-clock ticks, 1 tick = 1 pool traversal):

    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --arrival-rate 0.25 --requests 16 --slo 120
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_kv_mesh
from repro.models import init_params
from repro.serve import traffic
from repro.serve.engine import MultiPortEngine


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
    the variable itself, so nothing else is set), else a fixed
    ``.jax_cache/`` at the checkout root — a fixed path, because the path
    is part of what a later run must find again. Call it from an entry
    point, never at import."""
    got = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if got:
        return got
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the architecture's small preset instead of "
                         "its published widths (the size for CPU runs)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="initial slot-table size")
    ap.add_argument("--max-slots", type=int, default=64,
                    help="slot-table growth bound (continuous batching)")
    ap.add_argument("--chunk-tokens", type=int, default=16,
                    help="prefill chunk size (tokens per admission per cycle)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seq-tile", type=int, default=None,
                    help="KV-cache tile size for length-bounded traversals "
                         "(default: min(64, max_len)); validated against "
                         "--max-len's bucket ladder at startup")
    ap.add_argument("--no-length-bound", action="store_true",
                    help="disable live-length bounding (stage full max_len "
                         "caches every step — the unbounded baseline)")
    ap.add_argument("--no-dynamic-grid", action="store_true",
                    help="fall back to the bucketed stage-length ladder "
                         "(one jit retrace per power-of-two tile bucket) "
                         "instead of the dynamic-grid kernels whose single "
                         "trace serves every cache length")
    ap.add_argument("--num-kv-splits", type=int, default=1,
                    help="split-KV flash-decode: run each sequence's decode "
                         "traversal as this many grid-parallel partial-"
                         "attention chains plus an LSE-combine step, so a "
                         "long context no longer bounds the step latency "
                         "(1 = today's serial traversal, the bit-exact "
                         "oracle; pallas decode only)")
    ap.add_argument("--kv-shards", type=int, default=1,
                    help="shard the paged KV pool page-aligned across this "
                         "many devices (data-parallel KV: device-aware page "
                         "allocation + shard_map'd pool/kernels); on CPU, "
                         "force host devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--single-port", action="store_true")
    ap.add_argument("--kernel-mode", default="pallas",
                    choices=["pallas", "reference"])
    ap.add_argument("--schedule-mode", default="ooo",
                    choices=["static", "ooo"],
                    help="macro-cycle port scheduler: 'ooo' co-schedules "
                         "non-hazarding phases (disjoint pages) into shared "
                         "pool traversals; 'static' keeps the rigid "
                         "one-traversal-per-phase walk (the oracle)")
    ap.add_argument("--max-ports", type=int, default=4,
                    help="per-traversal port budget (1-4, the paper's B1B0 "
                         "knob); 1 degrades the attention compute to the "
                         "two-pass W-then-R oracle")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-loop mode: seeded Poisson arrivals at this "
                         "many requests per virtual tick (1 tick = 1 pool "
                         "traversal), heavy-tailed lengths over the "
                         "registry scenario spread; requests are admitted "
                         "FIFO as slots free up instead of being submitted "
                         "all at once")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="open-loop mode: replay a JSONL arrival trace "
                         "(see repro.serve.traffic.write_trace) instead of "
                         "the Poisson generator")
    ap.add_argument("--slo", type=float, default=None, metavar="TICKS",
                    help="p99-TTFT SLO in virtual-clock ticks: print "
                         "attainment (fraction of requests whose TTFT met "
                         "it) with the open-loop latency summary")
    ap.add_argument("--deadline", type=float, default=None, metavar="TICKS",
                    help="admission TTL in virtual ticks: a request still "
                         "queued past arrival+TTL is SHED (head-only, "
                         "counted) instead of admitted — overload-safe "
                         "serving's deadline stage")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="bound the admission queue: submissions beyond "
                         "this depth are rejected immediately "
                         "(shed_reason='queue_full') rather than queued "
                         "into unbounded delay")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="open-loop only: inject a seeded FaultPlan "
                         "(capacity squeezes, mid-stream cancels, delayed "
                         "retirement) through serve.chaos.ChaosHarness "
                         "with engine/pool invariant audits after every "
                         "fault")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable refcounted prefix sharing: every "
                         "admission prefills its full prompt even when an "
                         "identical prefix is already resident (the "
                         "launcher serves with the prefix cache ON by "
                         "default; tokens are bit-identical either way)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.trace and args.arrival_rate is not None:
        raise SystemExit("--trace and --arrival-rate are exclusive")
    enable_compile_cache()

    cfg = registry.get(args.arch, reduced=args.reduced)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{args.arch} has a stub frontend; serve a token arch")
    seq_tile = (min(64, args.max_len) if args.seq_tile is None
                else args.seq_tile)
    # validate against the engine's OWN ladder construction (clamp
    # included) — the ladder it keeps through max_slots growth — not a
    # hand-rolled snapshot that silently diverged from the engine's actual
    # staging geometry (the old validation skipped the engine's
    # seq_tile=min(seq_tile, max_len) clamp)
    try:
        buckets = MultiPortEngine.final_stage_ladder(args.max_len, seq_tile)
    except ValueError as e:
        raise SystemExit(f"--seq-tile: {e}")
    if seq_tile > args.max_len:
        print(f"--seq-tile {seq_tile} exceeds --max-len {args.max_len}; "
              f"clamping to {args.max_len} (the engine's own clamp)")
        seq_tile = args.max_len
    if args.num_kv_splits < 1:
        raise SystemExit(f"--num-kv-splits must be >= 1, "
                         f"got {args.num_kv_splits}")
    grid = "bucketed" if args.no_dynamic_grid else "dynamic-grid"
    print(f"length-bounded staging buckets (seq_tile={seq_tile}, "
          f"S_max={args.max_len}, {grid}): {list(buckets)}")
    if args.num_kv_splits > 1:
        print(f"split-KV flash-decode: {args.num_kv_splits} partial chains "
              f"per sequence + LSE combine (pallas decode path)")
    mesh = None
    if args.kv_shards > 1:
        try:
            mesh = make_kv_mesh(args.kv_shards)
        except ValueError as e:
            raise SystemExit(f"--kv-shards: {e}")
        print(f"data-parallel KV: pool sharded page-aligned over "
              f"{args.kv_shards} devices ({[str(d) for d in mesh.devices.flat]})")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = MultiPortEngine(params, cfg, slots=args.slots,
                          max_slots=max(args.max_slots, args.slots),
                          max_len=args.max_len,
                          chunk_tokens=args.chunk_tokens,
                          kernel_mode=args.kernel_mode,
                          single_port=args.single_port,
                          seq_tile=seq_tile,
                          length_bound=not args.no_length_bound,
                          dynamic_grid=not args.no_dynamic_grid,
                          num_kv_splits=args.num_kv_splits,
                          mesh=mesh,
                          schedule_mode=args.schedule_mode,
                          max_ports=args.max_ports,
                          default_ttl_ticks=args.deadline,
                          max_queue_depth=args.max_queue_depth,
                          prefix_cache=not args.no_prefix_cache)
    open_loop = args.trace is not None or args.arrival_rate is not None
    if args.chaos_seed is not None and not open_loop:
        raise SystemExit("--chaos-seed needs open-loop mode "
                         "(--arrival-rate or --trace)")
    if open_loop:
        if args.trace:
            arrivals = traffic.trace_arrivals(args.trace, vocab=cfg.vocab,
                                              seed=args.seed)
        else:
            max_prompt = max(args.max_len - args.max_new, 2)
            arrivals = traffic.poisson_arrivals(
                args.requests, args.arrival_rate, seed=args.seed,
                vocab=cfg.vocab, max_prompt=min(40, max_prompt),
                max_output=args.max_new)
        for a in arrivals:
            if a.prompt_len + a.max_new > args.max_len:
                raise SystemExit(
                    f"arrival ({a.prompt_len}+{a.max_new}) exceeds "
                    f"--max-len {args.max_len}")
        print(f"open-loop: {len(arrivals)} arrivals over ticks "
              f"[{arrivals[0].arrival_tick}, {arrivals[-1].arrival_tick}]"
              if arrivals else "open-loop: empty schedule")
        harness = None
        if args.chaos_seed is not None:
            from repro.serve.chaos import ChaosHarness, FaultPlan
            horizon = (arrivals[-1].arrival_tick + 1) if arrivals else 1
            harness = ChaosHarness(
                FaultPlan.generate(args.chaos_seed, horizon=horizon))
        t0 = time.perf_counter()
        traffic.drive(eng, arrivals, on_cycle=harness)
        if harness is not None:
            harness.finalize(eng)
        dt = time.perf_counter() - t0
        done = eng.finished
    else:
        rng = np.random.default_rng(args.seed)
        for _ in range(args.requests):
            eng.submit(list(rng.integers(0, cfg.vocab,
                                         int(rng.integers(3, 10)))),
                       max_new=args.max_new)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    mode = "single-port" if args.single_port else "multi-port"
    print(f"[{mode}] {len(done)} requests, {toks} tokens, "
          f"{eng.cycles} macro-cycles, {dt:.2f}s ({toks/max(dt,1e-9):.1f} tok/s)")
    print(f"pool traversals: {eng.pool_traversals} "
          f"({eng.pool_traversals / max(toks, 1):.2f}/token); "
          f"slots grown to {eng.n_slots}/{eng.max_slots}; prefill "
          f"{eng.prefill_traversals / max(eng.prefill_tokens, 1):.3f} "
          f"traversals/prompt-token over {eng.prefill_steps} chunk cycles")
    print(f"jit traces: decode {eng.decode_traces}, prefill-chunk "
          f"{eng.prefill_traces} (dynamic grid: {eng.dynamic_grid})")
    mixes = ", ".join(f"{k}: {v}" for k, v in
                      sorted(eng.pool.mix_counts.items()))
    print(f"schedule [{eng.schedule_mode}, max_ports={eng.max_ports}]: "
          f"{eng.coscheduled_cycles}/{eng.multi_phase_cycles} multi-phase "
          f"cycles co-scheduled (frac {eng.coschedule_frac:.2f}); "
          f"traversal mixes {{{mixes}}}")
    print(f"tile reads (seq_tile={eng.seq_tile}): decode "
          f"{eng.steady_decode_tile_reads} steady "
          f"(bound {eng.steady_decode_tile_bound}), prefill "
          f"{eng.prefill_tile_reads / max(eng.prefill_chunks, 1):.2f}/chunk "
          f"vs {-(-args.max_len // eng.seq_tile)} dense; pool "
          f"r/w {eng.pool.tile_reads}/{eng.pool.tile_writes}")
    if eng.n_kv_shards > 1:
        print(f"kv shards: {eng.n_kv_shards} "
              f"(pages/shard {eng.pool.plan.pages_per_shard}); steady decode "
              f"tile reads by device {eng.steady_decode_tile_reads_by_dev} "
              f"(balance {eng.kv_tile_balance:.2f}x ideal); pool tiles r/w "
              f"by shard {eng.pool.tile_reads_by_shard}/"
              f"{eng.pool.tile_writes_by_shard}")
    if eng.prefix_cache:
        ps = eng.prefix_stats
        print(f"prefix cache: {ps['hits']}/{ps['lookups']} admissions "
              f"attached a resident prefix ({ps['attached_tokens']} tokens "
              f"/ {ps['attached_pages']} pages adopted without recompute); "
              f"copy-on-write splits {ps['cow_copies']} "
              f"({ps['cow_words']} words copied)")
    if open_loop:
        ttft = np.array([r.ttft_ticks for r in done
                         if r.ttft_ticks is not None], dtype=np.float64)
        tpot = np.array([r.tpot_ticks for r in done
                         if r.tpot_ticks is not None], dtype=np.float64)
        if ttft.size:
            line = (f"latency (virtual ticks, 1 tick = 1 pool traversal): "
                    f"TTFT p50/p99 {np.percentile(ttft, 50):.1f}/"
                    f"{np.percentile(ttft, 99):.1f}")
            if tpot.size:
                line += (f"; per-token p50/p99 {np.percentile(tpot, 50):.2f}/"
                         f"{np.percentile(tpot, 99):.2f}")
            print(line)
        print(f"queue: peak depth {eng.admission.peak_depth}, "
              f"slot-contention cycles {eng.slot_contention_cycles}, "
              f"evict-pressure admissions {eng.evict_pressure_admissions}, "
              f"total ticks {eng.vclock}")
        if eng.shed or eng.cancelled or eng.capacity_parked_cycles:
            print(f"overload: shed {len(eng.shed)} "
                  f"(deadline {eng.shed_deadline}, queue_full "
                  f"{eng.shed_queue_full}, capacity {eng.shed_capacity}), "
                  f"capacity parked/recovered "
                  f"{eng.capacity_parked_cycles}/{eng.capacity_recoveries}, "
                  f"cancelled {eng.cancelled}")
        if harness is not None:
            print(f"chaos [seed {args.chaos_seed}]: "
                  f"{len(harness.injected)} actions, "
                  f"{harness.invariant_checks} invariant audits clean, "
                  f"stalled retirements {eng.stalled_retirements}, "
                  f"straggler events {harness.straggler_events}")
        if args.slo is not None and ttft.size:
            met = int((ttft <= args.slo).sum())
            print(f"SLO (p99 TTFT <= {args.slo:g} ticks): "
                  f"{'MET' if np.percentile(ttft, 99) <= args.slo else 'MISSED'}"
                  f" — {met}/{ttft.size} requests within SLO")
    for r in done[:4]:
        print(f"  req {r.rid}: prompt={r.prompt} -> {r.generated}")


if __name__ == "__main__":
    main()
