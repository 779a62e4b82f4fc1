"""System-level claim C1: the multi-port engine's fused (pallas) data plane
completes a request batch with ONE pool traversal per decode step where the
two-pass reference does >= 2, and the 4-port schedule finishes in fewer
macro-cycles (and less wall time) than single-port scheduling.

Reported per mode: macro-cycles, wall seconds, generated tokens,
cycles/token, physical pool traversals, traversals/token,
traversals-per-decode-step (the headline C1 ratio: ~1 fused vs >= 2
reference), and seq_tile-tile reads per steady decode step (the
length-bounded-traversal metric: the fused kernel touches only live tiles).

A second section measures chunked batched prefill: admissions split into
fixed-size chunks share ONE bulk-write pool transaction per macro-cycle, so
prefill pool-traversals-per-admitted-token shrinks as the admission batch
grows — and the fused chunk kernel reads only live tiles per chunk where the
dense reference reads the whole S_max staging cache.

A third section sweeps decode tile reads against cache length: the
length-bounded kernel's read traffic tracks cache_len while the unbounded
kernel pays the full allocated capacity every step (>= 4x fewer tile reads
at cache_len = S_max/8).

A fourth section counts JIT TRACES across a cache-length sweep: the
dynamic-grid kernels (live bound read from SMEM at run time) serve every
cache length from ONE decode trace, where the bucketed fallback retraces
once per power-of-two stage-length bucket.

A fifth section measures DATA-PARALLEL KV: the paged
pool sharded page-aligned across a ``kv`` mesh (forced host devices on CPU
CI), kernels shard_map'd by home device. It reports the per-device steady-
decode tile-read balance (max device / per-device mean; 1.0 = ideal) and
re-checks the headline gates UNDER SHARDING: fused-vs-reference traversal
ratio, the tile budget, the bounded-vs-unbounded tile ratio, the
single-trace property, and token identity against the unsharded engine.
Needs > 1 visible device (``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` on CPU); with one device the section records itself as skipped
and the sharded gates no-op.

A sixth section (this schema revision) measures the CONFIGURABLE PORT MIX:
a mixed prefill+decode workload with STAGGERED prompt lengths keeps some
slots mid-prefill while others decode, and the dependency-tracked macro-
cycle scheduler (``schedule_mode='ooo'``) merges hazard-free phases —
eviction frees, bulk-fill prefill writes, decode append/read of disjoint
pages — into shared pool traversals with arbitrary 1-4-port mixes. It
reports pool traversals per macro-cycle and per token, the co-scheduled
fraction of multi-phase cycles, and the per-mix traversal histogram
(e.g. ``3-port[2W+1R|...]``) against the rigid one-traversal-per-phase
``'static'`` walk and against reduced port budgets (``max_ports`` = 2, 1).

A seventh section (this schema revision) measures SPLIT-KV FLASH-DECODE on
a LONG-CONTEXT workload: one near-capacity prompt among short ones makes a
single row's serial tile chain the critical path of every steady decode
step. ``num_kv_splits`` partitions each row's live range into grid-parallel
partial-attention banks (combined by a second LSE pass), so the critical
path shrinks to ``ceil(chain / splits) + 1`` while the tiles SERVICED stay
identical — the latency proxy (critical-path tiles per steady decode step)
is what improves, the bandwidth accounting is unchanged, and greedy decode
stays token-identical at every split count.

CI gate (see .github/workflows/ci.yml bench-smoke and benchmarks/README.md):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/engine_bench.py --json BENCH_engine.json \
        --min-traversal-ratio 1.9 --enforce-tile-bound --min-tile-ratio 3.9 \
        --enforce-single-trace --max-kv-balance 1.25 \
        --min-coschedule-frac 0.75 --min-split-speedup 2.0

writes the ``bench-engine/v6`` record and exits non-zero if the fused-vs-
reference steady-decode traversal ratio, the steady-decode tile budget
(ceil((cache_len+1)/seq_tile) per step), the bounded-vs-unbounded tile
ratio at cache_len = S_max/8, the single-trace property of the dynamic-grid
decode path, the sharded per-device tile-read balance, the scheduler's
co-scheduled-cycle fraction / traversals-per-cycle advantage, or the
split-KV critical-path speedup on the long-context sweep regresses.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import registry
from repro.models import init_params
from repro.serve.engine import MultiPortEngine

MODES = (
    # (name, kernel_mode, single_port)
    ("pallas", "pallas", False),
    ("reference", "reference", False),
    ("single_port", "reference", True),
)

PREFILL_BATCHES = (1, 4, 8)

# tile sweep workload: S_max and the tile size the decode kernel traverses
TILE_S_MAX = 64
TILE_SEQ = 8
# steady decode cache_len targets as fractions of S_max
TILE_FRACS = (8, 4, 2)


def _setup():
    cfg = registry.get("tinyllama-1.1b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def run(n_requests: int = 8, max_new: int = 6) -> dict:
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, int(rng.integers(3, 8))))
               for _ in range(n_requests)]

    out = {}
    tokens_by_mode = {}
    for mode, kernel_mode, single in MODES:
        eng = MultiPortEngine(params, cfg, slots=4, max_len=64,
                              prefill_bucket=8, seq_tile=TILE_SEQ,
                              kernel_mode=kernel_mode, single_port=single)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run(max_cycles=5000)
        dt = time.perf_counter() - t0
        assert len(done) == n_requests
        toks = sum(len(r.generated) for r in done)
        tokens_by_mode[mode] = {r.rid: tuple(r.generated) for r in done}
        steady = max(eng.steady_decode_steps, 1)
        out[mode] = {
            "cycles": eng.cycles, "seconds": dt, "tokens": toks,
            "cycles_per_token": eng.cycles / toks,
            "pool_traversals": eng.pool_traversals,
            "traversals_per_token": eng.pool_traversals / toks,
            "traversals_per_decode": (eng.decode_traversals
                                      / max(eng.decode_steps, 1)),
            # steady state: decode cycles carrying both append + read ports
            "traversals_per_decode_steady": (eng.steady_decode_traversals
                                             / steady),
            # length-bounded traversal accounting (seq_tile tiles the decode
            # R port touches vs the ideal ceil((cache_len+1)/seq_tile) budget)
            "seq_tile": eng.seq_tile,
            "tile_reads": eng.decode_tile_reads,
            "tile_reads_per_decode_steady": (eng.steady_decode_tile_reads
                                             / steady),
            "tile_bound_per_decode_steady": (eng.steady_decode_tile_bound
                                             / steady),
            "within_tile_bound": (eng.steady_decode_tile_reads
                                  <= eng.steady_decode_tile_bound),
            "pool_tile_reads": eng.pool.tile_reads,
            "pool_tile_writes": eng.pool.tile_writes,
            # jit retraces of the decode / chunk steps over the whole run
            "decode_traces": eng.decode_traces,
            "prefill_traces": eng.prefill_traces,
            "dynamic_grid": eng.dynamic_grid,
        }
    # all modes must agree token-for-token (same greedy decode)
    assert (tokens_by_mode["pallas"] == tokens_by_mode["reference"]
            == tokens_by_mode["single_port"]), "modes disagree on tokens"
    out["cycle_ratio"] = (out["single_port"]["cycles"]
                          / out["pallas"]["cycles"])
    out["traversal_ratio"] = (
        out["reference"]["traversals_per_decode_steady"]
        / out["pallas"]["traversals_per_decode_steady"])
    return out


def run_prefill(batch_sizes=PREFILL_BATCHES, prompt_len: int = 24,
                chunk_tokens: int = 8) -> dict:
    """Chunked batched prefill: pool traversals per admitted prompt token as
    the concurrent admission batch grows (slot pool growing past the seed's
    4 along the way), plus tile reads per chunk — the fused chunk kernel
    touches only live tiles where the dense reference reads all of S_max."""
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    dense_tiles = -(-TILE_S_MAX // TILE_SEQ)
    out = {"prompt_len": prompt_len, "chunk_tokens": chunk_tokens,
           "seq_tile": TILE_SEQ, "dense_tiles_per_chunk": dense_tiles,
           "per_batch": {}}
    for n in batch_sizes:
        eng = MultiPortEngine(params, cfg, slots=1, max_slots=max(n, 1),
                              max_len=TILE_S_MAX, chunk_tokens=chunk_tokens,
                              seq_tile=TILE_SEQ)
        for _ in range(n):
            eng.submit(list(rng.integers(0, cfg.vocab, prompt_len)),
                       max_new=1)
        t0 = time.perf_counter()
        done = eng.run(max_cycles=2000)
        dt = time.perf_counter() - t0
        assert len(done) == n
        out["per_batch"][str(n)] = {
            "seconds": dt,
            "prefill_tokens": eng.prefill_tokens,
            "prefill_cycles": eng.prefill_steps,
            "prefill_traversals": eng.prefill_traversals,
            "traversals_per_token": (eng.prefill_traversals
                                     / max(eng.prefill_tokens, 1)),
            "tile_reads_per_chunk": (eng.prefill_tile_reads
                                     / max(eng.prefill_chunks, 1)),
            "grown_slots": eng.n_slots,
        }
    return out


def measure_kernel_tiles() -> dict:
    """Direct KERNEL-MEASURED serviced-tile check — the teeth behind
    ``--enforce-tile-bound``. The engine's per-step counters are host-side
    accounting of the kernels' skip formula; this probe asks the kernels
    themselves (``return_tiles``) how many tiles they serviced for a
    steady-decode-shaped batch (including a dead padded row) and for one
    prefill chunk, and compares against the ceil budgets. A kernel
    regression that stops skipping dead tiles fails HERE, in the bench job,
    independent of the tier-1 suite."""
    import jax.numpy as jnp

    from repro.kernels.kv_multiport import fused_append_attend
    from repro.kernels.kv_prefill_chunk import fused_chunk_append_attend

    rng = np.random.default_rng(3)
    s, tile, hkv, g, d = TILE_S_MAX, TILE_SEQ, 2, 2, 16
    h = hkv * g

    lens = np.array([s // 8, s // 4, s // 2 - 1, -1])     # last row = padding
    q = jnp.asarray(rng.normal(size=(4, h, d)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(4, s, hkv, d)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(4, s, hkv, d)), jnp.float32)
    nk = jnp.asarray(rng.normal(size=(4, hkv, d)), jnp.float32)
    nv = jnp.asarray(rng.normal(size=(4, hkv, d)), jnp.float32)
    *_, dec = fused_append_attend(q, ck, cv, nk, nv,
                                  jnp.asarray(lens, jnp.int32),
                                  seq_tile=tile, return_tiles=True)
    dec_budget = [int(-(-(p + 1) // tile)) if p >= 0 else 0 for p in lens]

    c = 4
    offs = np.array([0, s // 4, -1])                      # last row = padding
    cls = np.array([c, c - 1, 0])
    qc = jnp.asarray(rng.normal(size=(3, c, h, d)), jnp.float32)
    ck3, cv3 = ck[:3], cv[:3]
    nk3 = jnp.asarray(rng.normal(size=(3, c, hkv, d)), jnp.float32)
    nv3 = jnp.asarray(rng.normal(size=(3, c, hkv, d)), jnp.float32)
    *_, pf = fused_chunk_append_attend(qc, ck3, cv3, nk3, nv3,
                                       jnp.asarray(offs, jnp.int32),
                                       jnp.asarray(cls, jnp.int32),
                                       seq_tile=tile, return_tiles=True)
    pf_budget = [int(-(-(o + n) // tile)) if o >= 0 else 0
                 for o, n in zip(offs, cls)]

    dec, pf = np.asarray(dec).tolist(), np.asarray(pf).tolist()
    return {"seq_tile": tile, "s_max": s,
            "decode_measured": dec, "decode_budget": dec_budget,
            "prefill_measured": pf, "prefill_budget": pf_budget,
            "within": (all(m <= b for m, b in zip(dec, dec_budget))
                       and all(m <= b for m, b in zip(pf, pf_budget)))}


def run_tiles(max_new: int = 4, requests: int = 4) -> dict:
    """Decode read traffic vs live cache length: steady-decode tile reads
    per step per slot for the length-bounded kernel against the unbounded
    traversal, at cache_len targets S_max/8, S_max/4, S_max/2."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    out = {"s_max": TILE_S_MAX, "seq_tile": TILE_SEQ, "per_cache_len": {}}

    def measure(prompt_len, length_bound):
        eng = MultiPortEngine(params, cfg, slots=requests,
                              max_len=TILE_S_MAX, seq_tile=TILE_SEQ,
                              chunk_tokens=8, length_bound=length_bound)
        for _ in range(requests):
            eng.submit(list(rng.integers(0, cfg.vocab, prompt_len)),
                       max_new=max_new)
        done = eng.run(max_cycles=2000)
        assert len(done) == requests
        steps = max(eng.steady_decode_steps, 1)
        return {
            "tile_reads_per_step": (eng.steady_decode_tile_reads
                                    / steps / requests),
            "tile_bound_per_step": (eng.steady_decode_tile_bound
                                    / steps / requests),
            "within_tile_bound": (eng.steady_decode_tile_reads
                                  <= eng.steady_decode_tile_bound),
            "decode_traces": eng.decode_traces,
        }

    for frac in TILE_FRACS:
        target = TILE_S_MAX // frac
        prompt_len = max(2, target - max_new // 2)
        bounded = measure(prompt_len, True)
        unbounded = measure(prompt_len, False)
        out["per_cache_len"][str(target)] = {
            "prompt_len": prompt_len,
            "bounded": bounded,
            "unbounded": unbounded,
            "tile_ratio": (unbounded["tile_reads_per_step"]
                           / max(bounded["tile_reads_per_step"], 1e-9)),
        }
    # headline: the ratio at cache_len = S_max/8
    out["tile_ratio_at_s8"] = (
        out["per_cache_len"][str(TILE_S_MAX // 8)]["tile_ratio"])
    out["kernel_measured"] = measure_kernel_tiles()
    return out


def run_kv_balance(n_requests: int = 8, prompt_len: int = 5,
                   max_new: int = 6) -> dict:
    """Data-parallel KV: shard the pool (and the kernels) across the
    largest power-of-two count of visible devices (<= 8) and measure the
    per-device steady-decode tile-read balance plus the headline gates
    UNDER SHARDING. Equal-length prompts and one request per slot make the
    ideal balance 1.0 — the gate budget (1.25x) leaves room only for
    admission-order skew, not systematic imbalance."""
    avail = len(jax.devices())
    shards = 1
    while shards * 2 <= min(avail, 8):
        shards *= 2
    out = {"available_devices": avail, "kv_shards": shards,
           "s_max": TILE_S_MAX, "seq_tile": TILE_SEQ,
           "prompt_len": prompt_len, "requests": n_requests}
    if shards == 1:
        out.update({"skipped": True, "balance": 1.0})
        return out

    from repro.launch.mesh import make_kv_mesh
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab, prompt_len))
               for _ in range(n_requests)]
    mesh = make_kv_mesh(shards)

    def serve(kernel_mode, use_mesh, length_bound=True):
        eng = MultiPortEngine(params, cfg, slots=n_requests,
                              max_len=TILE_S_MAX, seq_tile=TILE_SEQ,
                              chunk_tokens=8, kernel_mode=kernel_mode,
                              length_bound=length_bound,
                              mesh=mesh if use_mesh else None)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        done = eng.run(max_cycles=2000)
        # completion/identity failures are RECORDED, not raised: the JSON
        # record and the gate diagnostics must materialize on regressions
        # too (CI uploads the artifact precisely when a gate fails)
        return eng, (len(done) == n_requests,
                     {r.rid: tuple(r.generated) for r in done})

    ep, (ok_p, tp) = serve("pallas", True)
    er, (ok_r, tr) = serve("reference", True)
    e1, (ok_1, t1) = serve("pallas", False)
    eu, (ok_u, tu) = serve("pallas", True, length_bound=False)
    steady = max(ep.steady_decode_steps, 1)
    out.update({
        "skipped": False,
        "completed": ok_p and ok_r and ok_1 and ok_u,
        "tokens_match_unsharded": tp == tr == t1 == tu
        and ok_p and ok_r and ok_1 and ok_u,
        "balance": ep.kv_tile_balance,
        "tile_reads_by_dev": list(ep.steady_decode_tile_reads_by_dev),
        "pool_tile_reads_by_shard": list(ep.pool.tile_reads_by_shard),
        "pool_tile_writes_by_shard": list(ep.pool.tile_writes_by_shard),
        "pages_per_shard": ep.pool.plan.pages_per_shard,
        # max(..., 1e-9) denominators: a stalled sharded engine must surface
        # as a failed gate with a written record, never a ZeroDivisionError
        "traversal_ratio": (er.steady_decode_traversals
                            / max(er.steady_decode_steps, 1)
                            / max(ep.steady_decode_traversals / steady,
                                  1e-9)),
        "within_tile_bound": (ep.steady_decode_tile_reads
                              <= ep.steady_decode_tile_bound),
        "tile_ratio": (eu.steady_decode_tile_reads
                       / max(eu.steady_decode_steps, 1)
                       / max(ep.steady_decode_tile_reads / steady, 1e-9)),
        "decode_traces": ep.decode_traces,
    })
    return out


SCHEDULE_PROMPT_LENS = (6, 14, 22, 30)


def run_schedule(prompt_lens=SCHEDULE_PROMPT_LENS, max_new: int = 10,
                 chunk_tokens: int = 8) -> dict:
    """Configurable per-cycle port mix: the dependency-tracked macro-cycle
    scheduler (``schedule_mode='ooo'``) against the rigid one-traversal-per-
    phase walk (``'static'``). STAGGERED prompt lengths with a small prefill
    chunk keep some slots mid-prefill while others decode, so macro-cycles
    carry evict + bulk-fill + decode phases together; the scheduler merges
    the hazard-free ones (disjoint page footprints) into shared pool
    traversals with up-to-4-port mixes (e.g. ``2W+1R``). Reported per
    config: pool traversals, traversals per macro-cycle and per token, the
    fraction of multi-phase cycles that actually co-scheduled, and the
    per-mix traversal histogram. Greedy decode must stay token-identical
    across every schedule mode, kernel mode, and port budget."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, plen)) for plen in prompt_lens]
    configs = (
        # (name, kernel_mode, schedule_mode, max_ports)
        ("pallas_ooo", "pallas", "ooo", 4),
        ("pallas_static", "pallas", "static", 4),
        ("reference_ooo", "reference", "ooo", 4),
        ("reference_static", "reference", "static", 4),
        ("pallas_ooo_2port", "pallas", "ooo", 2),
        ("pallas_ooo_1port", "pallas", "ooo", 1),
    )
    out = {"prompt_lens": list(prompt_lens), "max_new": max_new,
           "chunk_tokens": chunk_tokens, "s_max": TILE_S_MAX,
           "seq_tile": TILE_SEQ, "per_config": {}}
    tokens_by_config = {}
    for name, kernel_mode, schedule_mode, max_ports in configs:
        eng = MultiPortEngine(params, cfg, slots=len(prompts),
                              max_len=TILE_S_MAX, seq_tile=TILE_SEQ,
                              chunk_tokens=chunk_tokens,
                              kernel_mode=kernel_mode,
                              schedule_mode=schedule_mode,
                              max_ports=max_ports)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run(max_cycles=2000)
        dt = time.perf_counter() - t0
        assert len(done) == len(prompts)
        toks = sum(len(r.generated) for r in done)
        tokens_by_config[name] = {r.rid: tuple(r.generated) for r in done}
        out["per_config"][name] = {
            "kernel_mode": kernel_mode, "schedule_mode": schedule_mode,
            "max_ports": max_ports, "seconds": dt, "tokens": toks,
            "cycles": eng.cycles,
            "pool_traversals": eng.pool_traversals,
            "traversals_per_cycle": eng.pool_traversals / max(eng.cycles, 1),
            "traversals_per_token": eng.pool_traversals / max(toks, 1),
            "multi_phase_cycles": eng.multi_phase_cycles,
            "coscheduled_cycles": eng.coscheduled_cycles,
            "coschedule_frac": eng.coschedule_frac,
            "mix_counts": dict(sorted(eng.pool.mix_counts.items())),
        }
    first = next(iter(tokens_by_config.values()))
    out["tokens_match"] = all(t == first for t in tokens_by_config.values())
    pc = out["per_config"]
    # headline: OOO pool traversals per macro-cycle vs the static oracle,
    # same kernel mode (pallas fused path)
    out["traversals_per_cycle_ooo"] = pc["pallas_ooo"]["traversals_per_cycle"]
    out["traversals_per_cycle_static"] = (
        pc["pallas_static"]["traversals_per_cycle"])
    out["coschedule_frac"] = pc["pallas_ooo"]["coschedule_frac"]
    return out


SPLIT_S_MAX = 128
SPLIT_COUNTS = (1, 2, 4)
SPLIT_PROMPT_LENS = (88, 6, 6, 6)


def run_split(prompt_lens=SPLIT_PROMPT_LENS, max_new: int = 4,
              splits=SPLIT_COUNTS) -> dict:
    """Split-KV flash-decode on a long-context sweep: ONE near-capacity
    prompt among short ones makes its serial tile chain (ceil(cache_len /
    seq_tile) tiles, walked in order for the online-softmax dependency) the
    critical path of every steady decode step. ``num_kv_splits`` breaks the
    chain into grid-parallel partial-attention banks plus one LSE-combine
    pass, so the latency proxy — critical-path tiles per steady decode step
    — drops toward ``ceil(chain / splits) + 1`` while tiles SERVICED (the
    bandwidth accounting the tile-bound gate budgets) are identical at
    every split count, and greedy decode stays token-identical."""
    cfg, params = _setup()
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(0, cfg.vocab, n)) for n in prompt_lens]
    out = {"s_max": SPLIT_S_MAX, "seq_tile": TILE_SEQ,
           "prompt_lens": list(prompt_lens), "max_new": max_new,
           "per_splits": {}}
    tokens = {}
    for ns in splits:
        eng = MultiPortEngine(params, cfg, slots=len(prompts),
                              max_len=SPLIT_S_MAX, seq_tile=TILE_SEQ,
                              chunk_tokens=8, num_kv_splits=ns)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run(max_cycles=2000)
        dt = time.perf_counter() - t0
        assert len(done) == len(prompts)
        tokens[ns] = {r.rid: tuple(r.generated) for r in done}
        steady = max(eng.steady_decode_steps, 1)
        out["per_splits"][str(ns)] = {
            "seconds": dt,
            "critical_tiles_per_step": (eng.steady_decode_critical_tiles
                                        / steady),
            "tile_reads_per_step": (eng.steady_decode_tile_reads / steady),
            "within_tile_bound": (eng.steady_decode_tile_reads
                                  <= eng.steady_decode_tile_bound),
        }
    base = out["per_splits"][str(splits[0])]
    best = out["per_splits"][str(max(splits))]
    out["tokens_match"] = all(t == tokens[splits[0]]
                              for t in tokens.values())
    # the split path must not change WHAT is read, only how it is chained
    out["tile_reads_match"] = all(
        x["tile_reads_per_step"] == base["tile_reads_per_step"]
        for x in out["per_splits"].values())
    out["split_speedup"] = (base["critical_tiles_per_step"]
                            / max(best["critical_tiles_per_step"], 1e-9))
    return out


def run_traces(prompt_lens=(6, 20, 40), max_new: int = 4,
               requests: int = 4) -> dict:
    """Retrace accounting across a cache-length sweep: the SAME engine
    serves waves of requests whose live lengths cross several stage-length
    buckets. The dynamic-grid path (default) keeps ONE decode trace — the
    live bound is a runtime scalar read from SMEM — while the bucketed
    fallback retraces once per power-of-two tile bucket it visits."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)

    def sweep(dynamic_grid):
        eng = MultiPortEngine(params, cfg, slots=requests,
                              max_len=TILE_S_MAX, seq_tile=TILE_SEQ,
                              chunk_tokens=8, dynamic_grid=dynamic_grid)
        for plen in prompt_lens:
            for _ in range(requests):
                eng.submit(list(rng.integers(0, cfg.vocab, plen)),
                           max_new=max_new)
            done = eng.run(max_cycles=2000)
        assert len(done) == requests * len(prompt_lens)
        return {"decode_traces": eng.decode_traces,
                "prefill_traces": eng.prefill_traces,
                "stage_lens": sorted(eng.stage_lens_seen),
                "steady_within_bound": (eng.steady_decode_tile_reads
                                        <= eng.steady_decode_tile_bound)}

    return {"s_max": TILE_S_MAX, "seq_tile": TILE_SEQ,
            "prompt_lens": list(prompt_lens),
            "dynamic": sweep(True), "bucketed": sweep(False)}


def report(r: dict, pf: dict, tl: dict, tr: dict, kv: dict,
           sc: dict, sk: dict) -> None:
    print("# serving engine: fused multi-port vs reference vs single-port "
          "(claim C1)")
    print("mode,cycles,seconds,tokens,cycles/token,pool_traversals,"
          "traversals/token,traversals/decode,traversals/decode(steady),"
          "tiles/decode(steady),tile_bound(steady),decode_traces")
    for m, _, _ in MODES:
        x = r[m]
        print(f"{m},{x['cycles']},{x['seconds']:.3f},{x['tokens']},"
              f"{x['cycles_per_token']:.2f},{x['pool_traversals']},"
              f"{x['traversals_per_token']:.2f},"
              f"{x['traversals_per_decode']:.2f},"
              f"{x['traversals_per_decode_steady']:.2f},"
              f"{x['tile_reads_per_decode_steady']:.2f},"
              f"{x['tile_bound_per_decode_steady']:.2f},"
              f"{x['decode_traces']}")
    print(f"cycle_ratio,{r['cycle_ratio']:.2f}")
    print(f"traversal_ratio,{r['traversal_ratio']:.2f}")
    print()
    print("# chunked batched prefill: pool traversals per admitted token "
          f"(prompt_len={pf['prompt_len']}, chunk={pf['chunk_tokens']}); "
          f"fused chunk tile reads vs {pf['dense_tiles_per_chunk']} dense "
          "tiles/chunk")
    print("batch,prefill_cycles,prefill_traversals,prefill_tokens,"
          "traversals/token,tiles/chunk,grown_slots")
    for n, x in pf["per_batch"].items():
        print(f"{n},{x['prefill_cycles']},{x['prefill_traversals']},"
              f"{x['prefill_tokens']},{x['traversals_per_token']:.3f},"
              f"{x['tile_reads_per_chunk']:.2f},{x['grown_slots']}")
    print()
    print("# length-bounded decode: steady tile reads/step/slot vs "
          f"cache_len (S_max={tl['s_max']}, seq_tile={tl['seq_tile']})")
    print("cache_len,bounded_tiles,unbounded_tiles,tile_bound,tile_ratio,"
          "decode_traces(bounded)")
    for cl, x in tl["per_cache_len"].items():
        print(f"{cl},{x['bounded']['tile_reads_per_step']:.2f},"
              f"{x['unbounded']['tile_reads_per_step']:.2f},"
              f"{x['bounded']['tile_bound_per_step']:.2f},"
              f"{x['tile_ratio']:.2f},{x['bounded']['decode_traces']}")
    print(f"tile_ratio_at_s8,{tl['tile_ratio_at_s8']:.2f}")
    km = tl["kernel_measured"]
    print(f"kernel_measured: decode {km['decode_measured']} <= "
          f"{km['decode_budget']}, prefill {km['prefill_measured']} <= "
          f"{km['prefill_budget']} -> within={km['within']}")
    print()
    print("# retrace accounting: one engine, cache lengths swept across "
          f"buckets (prompt_lens={tr['prompt_lens']}, S_max={tr['s_max']}, "
          f"seq_tile={tr['seq_tile']})")
    print("path,decode_traces,prefill_traces,stage_lens")
    for name in ("dynamic", "bucketed"):
        x = tr[name]
        print(f"{name},{x['decode_traces']},{x['prefill_traces']},"
              f"{'/'.join(map(str, x['stage_lens']))}")
    print()
    print("# configurable port mix: dependency-tracked scheduler (ooo) vs "
          f"rigid walk (static); staggered prompts {sc['prompt_lens']}, "
          f"chunk={sc['chunk_tokens']}, max_new={sc['max_new']}")
    print("config,cycles,pool_traversals,traversals/cycle,traversals/token,"
          "coscheduled/multi_phase,coschedule_frac,mixes")
    for name, x in sc["per_config"].items():
        mixes = " ".join(f"{k}:{v}" for k, v in x["mix_counts"].items())
        print(f"{name},{x['cycles']},{x['pool_traversals']},"
              f"{x['traversals_per_cycle']:.3f},"
              f"{x['traversals_per_token']:.3f},"
              f"{x['coscheduled_cycles']}/{x['multi_phase_cycles']},"
              f"{x['coschedule_frac']:.2f},{mixes}")
    print(f"tokens_match,{sc['tokens_match']}")
    print()
    print("# split-KV flash-decode: critical-path tiles per steady decode "
          f"step vs num_kv_splits (prompts {sk['prompt_lens']}, "
          f"S_max={sk['s_max']}, seq_tile={sk['seq_tile']})")
    print("num_kv_splits,critical_tiles/step,tile_reads/step,"
          "within_tile_bound")
    for ns, x in sk["per_splits"].items():
        print(f"{ns},{x['critical_tiles_per_step']:.2f},"
              f"{x['tile_reads_per_step']:.2f},{x['within_tile_bound']}")
    print(f"split_speedup,{sk['split_speedup']:.2f}")
    print(f"tokens_match,{sk['tokens_match']}")
    print(f"tile_reads_match,{sk['tile_reads_match']}")
    print()
    print(f"# data-parallel KV: pool page-aligned over {kv['kv_shards']} "
          f"device(s) of {kv['available_devices']} visible "
          f"(S_max={kv['s_max']}, seq_tile={kv['seq_tile']})")
    if kv.get("skipped"):
        print("skipped: needs > 1 device (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 before jax init)")
    else:
        print("tile_reads_by_dev,balance,traversal_ratio,tile_ratio,"
              "within_tile_bound,decode_traces,tokens_match_unsharded")
        print(f"{'/'.join(map(str, kv['tile_reads_by_dev']))},"
              f"{kv['balance']:.2f},{kv['traversal_ratio']:.2f},"
              f"{kv['tile_ratio']:.2f},{kv['within_tile_bound']},"
              f"{kv['decode_traces']},{kv['tokens_match_unsharded']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the bench-engine/v6 record (BENCH_engine.json)")
    ap.add_argument("--min-traversal-ratio", type=float, default=None,
                    help="exit non-zero if fused-vs-reference steady-decode "
                         "traversal ratio drops below this gate")
    ap.add_argument("--enforce-tile-bound", action="store_true",
                    help="exit non-zero if fused steady-decode tile reads "
                         "exceed ceil((cache_len+1)/seq_tile) per step")
    ap.add_argument("--min-tile-ratio", type=float, default=None,
                    help="exit non-zero if bounded-vs-unbounded decode tile "
                         "reads at cache_len=S_max/8 drop below this gate")
    ap.add_argument("--enforce-single-trace", action="store_true",
                    help="exit non-zero if the dynamic-grid decode path "
                         "needs more than ONE jit trace across the "
                         "cache-length sweep")
    ap.add_argument("--min-coschedule-frac", type=float, default=None,
                    help="exit non-zero if the ooo scheduler co-schedules "
                         "fewer than this fraction of multi-phase macro-"
                         "cycles on the mixed prefill+decode workload, if "
                         "ooo fails to commit strictly fewer pool "
                         "traversals per macro-cycle than the static walk, "
                         "or if any schedule config disagrees on tokens")
    ap.add_argument("--max-kv-balance", type=float, default=None,
                    help="exit non-zero if the sharded per-device steady-"
                         "decode tile-read balance (max/mean) exceeds this, "
                         "or any sharded headline gate (traversal/tile/"
                         "trace/token identity) regresses; skipped with a "
                         "warning when only one device is visible")
    ap.add_argument("--min-split-speedup", type=float, default=None,
                    help="exit non-zero if split-KV decode's critical-path "
                         "latency proxy on the long-context sweep improves "
                         "by less than this factor at the largest split "
                         "count, if the split path changes the serviced "
                         "tile accounting, or if any split count disagrees "
                         "on tokens")
    args = ap.parse_args(argv)

    r = run(args.requests, args.max_new)
    pf = run_prefill()
    tl = run_tiles()
    tr = run_traces()
    kv = run_kv_balance()
    sc = run_schedule()
    sk = run_split()
    report(r, pf, tl, tr, kv, sc, sk)

    # the gate combines the engine's accounting invariant with the DIRECT
    # kernel-measured serviced-tile probe (the part that can actually catch
    # a kernel that stops skipping dead tiles)
    tile_bound_ok = (r["pallas"]["within_tile_bound"]
                     and all(x["bounded"]["within_tile_bound"]
                             for x in tl["per_cache_len"].values())
                     and tl["kernel_measured"]["within"])
    if args.json:
        per_tok = [pf["per_batch"][str(n)]["traversals_per_token"]
                   for n in PREFILL_BATCHES]
        record = {
            "schema": "bench-engine/v6",
            "config": {"arch": "tinyllama-1.1b", "reduced": True,
                       "requests": args.requests, "max_new": args.max_new,
                       "seq_tile": TILE_SEQ, "s_max": TILE_S_MAX},
            "decode": {m: r[m] for m, _, _ in MODES},
            "cycle_ratio": r["cycle_ratio"],
            "traversal_ratio": r["traversal_ratio"],
            "prefill": pf,
            "tiles": tl,
            "traces": tr,
            "kv": kv,
            "schedule": sc,
            "split": sk,
            "gate": {
                "min_traversal_ratio": args.min_traversal_ratio,
                "traversal_ratio": r["traversal_ratio"],
                "prefill_traversals_per_token_monotonic":
                    all(a >= b for a, b in zip(per_tok, per_tok[1:])),
                "enforce_tile_bound": args.enforce_tile_bound,
                "within_tile_bound": tile_bound_ok,
                "min_tile_ratio": args.min_tile_ratio,
                "tile_ratio_at_s8": tl["tile_ratio_at_s8"],
                "enforce_single_trace": args.enforce_single_trace,
                "dynamic_decode_traces": tr["dynamic"]["decode_traces"],
                "max_kv_balance": args.max_kv_balance,
                "kv_balance": kv["balance"],
                "kv_shards": kv["kv_shards"],
                "min_coschedule_frac": args.min_coschedule_frac,
                "coschedule_frac": sc["coschedule_frac"],
                "traversals_per_cycle_ooo": sc["traversals_per_cycle_ooo"],
                "traversals_per_cycle_static":
                    sc["traversals_per_cycle_static"],
                "schedule_tokens_match": sc["tokens_match"],
                "min_split_speedup": args.min_split_speedup,
                "split_speedup": sk["split_speedup"],
                "split_tokens_match": sk["tokens_match"],
                "split_tile_reads_match": sk["tile_reads_match"],
            },
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"\nwrote {args.json}")

    failed = False
    if args.min_traversal_ratio is not None:
        if r["traversal_ratio"] < args.min_traversal_ratio:
            print(f"GATE FAIL: traversal_ratio {r['traversal_ratio']:.2f} < "
                  f"{args.min_traversal_ratio}", file=sys.stderr)
            failed = True
        else:
            print(f"GATE OK: traversal_ratio {r['traversal_ratio']:.2f} >= "
                  f"{args.min_traversal_ratio}")
    if args.enforce_tile_bound:
        if not tile_bound_ok:
            print("GATE FAIL: steady-decode tile reads exceed "
                  "ceil((cache_len+1)/seq_tile) per step", file=sys.stderr)
            failed = True
        else:
            print("GATE OK: steady-decode tile reads within the "
                  "ceil((cache_len+1)/seq_tile) budget")
    if args.min_tile_ratio is not None:
        if tl["tile_ratio_at_s8"] < args.min_tile_ratio:
            print(f"GATE FAIL: tile_ratio at S_max/8 "
                  f"{tl['tile_ratio_at_s8']:.2f} < {args.min_tile_ratio}",
                  file=sys.stderr)
            failed = True
        else:
            print(f"GATE OK: tile_ratio at S_max/8 "
                  f"{tl['tile_ratio_at_s8']:.2f} >= {args.min_tile_ratio}")
    if args.enforce_single_trace:
        dyn = tr["dynamic"]["decode_traces"]
        sweep_traces = [x["bounded"]["decode_traces"]
                        for x in tl["per_cache_len"].values()]
        if dyn != 1 or any(t != 1 for t in sweep_traces):
            print(f"GATE FAIL: dynamic-grid decode path retraced "
                  f"(sweep: {dyn}, per-cache-len: {sweep_traces}; want 1)",
                  file=sys.stderr)
            failed = True
        else:
            print("GATE OK: 1 decode trace across the cache-length sweep "
                  f"(bucketed fallback: {tr['bucketed']['decode_traces']})")
    if args.max_kv_balance is not None:
        if kv.get("skipped"):
            print("GATE SKIP: kv balance needs > 1 visible device (set "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        else:
            sharded_ok = (kv["tokens_match_unsharded"]
                          and kv["within_tile_bound"]
                          and (args.min_traversal_ratio is None
                               or kv["traversal_ratio"]
                               >= args.min_traversal_ratio)
                          and (args.min_tile_ratio is None
                               or kv["tile_ratio"] >= args.min_tile_ratio)
                          and (not args.enforce_single_trace
                               or kv["decode_traces"] in (-1, 1)))
            if kv["balance"] > args.max_kv_balance or not sharded_ok:
                print(f"GATE FAIL: data-parallel KV over {kv['kv_shards']} "
                      f"devices — balance {kv['balance']:.2f} (max "
                      f"{args.max_kv_balance}), traversal_ratio "
                      f"{kv['traversal_ratio']:.2f}, tile_ratio "
                      f"{kv['tile_ratio']:.2f}, within_tile_bound "
                      f"{kv['within_tile_bound']}, decode_traces "
                      f"{kv['decode_traces']}, tokens_match "
                      f"{kv['tokens_match_unsharded']}", file=sys.stderr)
                failed = True
            else:
                print(f"GATE OK: kv balance {kv['balance']:.2f} <= "
                      f"{args.max_kv_balance} over {kv['kv_shards']} devices "
                      f"(sharded traversal {kv['traversal_ratio']:.2f}x, "
                      f"tile {kv['tile_ratio']:.2f}x, traces "
                      f"{kv['decode_traces']})")
    if args.min_coschedule_frac is not None:
        frac = sc["coschedule_frac"]
        ooo_tc = sc["traversals_per_cycle_ooo"]
        static_tc = sc["traversals_per_cycle_static"]
        if (frac < args.min_coschedule_frac or ooo_tc >= static_tc
                or not sc["tokens_match"]):
            print(f"GATE FAIL: schedule — coschedule_frac {frac:.2f} (min "
                  f"{args.min_coschedule_frac}), traversals/cycle ooo "
                  f"{ooo_tc:.3f} vs static {static_tc:.3f} (want strictly "
                  f"fewer), tokens_match {sc['tokens_match']}",
                  file=sys.stderr)
            failed = True
        else:
            print(f"GATE OK: ooo co-scheduled {frac:.2f} of multi-phase "
                  f"cycles (min {args.min_coschedule_frac}) and committed "
                  f"{ooo_tc:.3f} traversals/cycle vs static {static_tc:.3f}, "
                  f"tokens identical across schedule configs")
    if args.min_split_speedup is not None:
        sp = sk["split_speedup"]
        if (sp < args.min_split_speedup or not sk["tokens_match"]
                or not sk["tile_reads_match"]):
            print(f"GATE FAIL: split-KV — speedup {sp:.2f} (min "
                  f"{args.min_split_speedup}), tokens_match "
                  f"{sk['tokens_match']}, tile_reads_match "
                  f"{sk['tile_reads_match']}", file=sys.stderr)
            failed = True
        else:
            print(f"GATE OK: split-KV critical-path speedup {sp:.2f}x >= "
                  f"{args.min_split_speedup} at num_kv_splits="
                  f"{max(SPLIT_COUNTS)}, tokens identical and serviced "
                  f"tiles unchanged across split counts")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
