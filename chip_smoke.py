#!/usr/bin/env python3
"""Smoke test of the serving engine on a TPU: full-width tinyllama-1.1b.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data-parallel KV over four chips

One process drives the normal serving path — open-loop arrivals through
``traffic.drive``, admission, the port scheduler, the paged pool and the
fused Pallas kernels compiled through Mosaic — with random weights from
``init_params(--seed)`` at the published widths (22 layers, d_model 2048,
32/4 heads, vocab 32000, bf16).

One chip: the compiled engine serves the requests, the same requests go
through ``kernel_mode="reference"`` (the jnp oracle under the same pool and
scheduler), and greedy tokens must agree. Where a request's tokens differ,
the first differing step is judged against a plain forward pass of the
shared prefix: both engines' tokens must sit within ``LOGIT_TOL`` of its
top logit. ``--chips 4`` runs only the four-chip path: the engine with its
pool and kernels sharded over a 4-device ``kv`` mesh against the unsharded
engine on one of those chips, judged the same way.

The last line of standard output is the JSON record
``{"ok": true, "device": {...}}``; any failed check exits non-zero first,
and no accelerator means exit 1 before anything runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "tinyllama-1.1b"
MAX_LEN = 512
SLOTS = 8
CHUNK = 64
SEQ_TILE = 64
N_REQUESTS = 8
MAX_NEW = 8
PROMPT = (32, 256)                 # prompt lengths, bounded-Pareto between
RATE = 0.1                         # arrivals per virtual tick
PREFIX = (2, 32)                   # shared headers: count, tokens each
# Both engines compute in bf16 (8-bit mantissa) and differ only in how the
# attention sums accumulate, which moves a final logit by a few bf16
# rounding steps of the logits' scale. A token divergence passes only when
# both chosen tokens are within 8 such steps (2**-5 of max |logit|) of the
# top logit of a plain forward pass over the shared prefix.
LOGIT_TOL = 2.0 ** -5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileMeter:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) and counts persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds, self.hits = 0.0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def build_engine(params, cfg, **kw):
    from repro.serve.engine import MultiPortEngine
    return MultiPortEngine(params, cfg, slots=SLOTS, max_slots=SLOTS,
                           max_len=MAX_LEN, chunk_tokens=CHUNK,
                           seq_tile=SEQ_TILE, prefix_cache=True, **kw)


def serve(name, eng, arrivals, meter) -> dict:
    """Drive one engine over the arrivals; returns rid -> tokens."""
    from repro.serve import traffic
    c0, h0 = meter.seconds, meter.hits
    t0 = time.perf_counter()
    res = traffic.drive(eng, arrivals)
    wall = time.perf_counter() - t0
    toks = {r.rid: list(r.generated) for r in eng.finished}
    n_tok = sum(len(t) for t in toks.values())
    print(f"{name}: served {res.served}/{res.submitted} requests, {n_tok} "
          f"tokens in {eng.cycles} macro-cycles; serve {wall:.1f}s "
          f"(compile {meter.seconds - c0:.1f}s, {meter.hits - h0} "
          f"persistent-cache hits); prefix cache {eng.prefix_stats}")
    if res.shed or res.served != res.submitted:
        fail(f"{name} shed or lost requests: {res}")
    short = [rid for rid, t in toks.items() if len(t) != MAX_NEW]
    if short:
        fail(f"{name}: requests {short} did not get {MAX_NEW} tokens")
    return toks


def compare(name, got, want, arrivals, params, cfg) -> None:
    """Tokens must agree; a divergence must be a near-tie of a plain
    forward pass at the first differing step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward

    logits_at = jax.jit(
        lambda p, ids, n: forward(p, cfg, {"inputs": ids})[0][0, n - 1])
    same = diverged = 0
    for rid, a in enumerate(arrivals):
        g, w = got[rid], want[rid]
        if g == w:
            same += 1
            continue
        k = next(i for i, (x, y) in enumerate(zip(g, w)) if x != y)
        seq = list(a.prompt) + g[:k]
        ids = np.zeros((1, MAX_LEN), np.int32)       # causal: pad after
        ids[0, :len(seq)] = seq
        z = np.asarray(logits_at(params, jnp.asarray(ids), len(seq)),
                       np.float32)
        tol = LOGIT_TOL * float(np.abs(z).max())
        gap = max(z.max() - z[g[k]], z.max() - z[w[k]])
        print(f"{name}: req {rid} first differs at token {k} "
              f"({g[k]} vs {w[k]}); plain-forward gap {gap:.4g} "
              f"(tol {tol:.4g})")
        if gap > tol:
            fail(f"{name}: req {rid} token {k} is not a near-tie "
                 f"(gap {gap:.4g} > {tol:.4g})")
        diverged += 1
    print(f"{name}: tokens identical for {same}/{len(arrivals)} requests, "
          f"{diverged} near-tie divergences within tolerance")


def decode_has_kernel(eng, cfg) -> bool:
    """True when the engine's compiled decode program holds a Mosaic
    kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp
    nl, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    s = eng.final_stage_ladder(MAX_LEN, SEQ_TILE)[-1]
    cache = jax.ShapeDtypeStruct((nl, SLOTS, s, hkv, hd), jnp.float32)
    state = {"len": jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
             "cache_k": cache, "cache_v": cache}
    batch = {"inputs": jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)}
    text = eng._decode.lower(eng.params, state, batch).compile().as_text()
    return "tpu_custom_call" in text


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel-KV path over four "
                         "chips against the unsharded engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the arrivals")
    args = ap.parse_args()

    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is on platform "
             f"{dev[0].platform!r}")
    if len(dev) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(dev)}")

    from repro.configs import registry
    from repro.launch.mesh import make_kv_mesh
    from repro.launch.serve import enable_compile_cache
    from repro.models import init_params
    from repro.serve import traffic

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    cfg = registry.get(ARCH)
    print(f"model {cfg.arch_id}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} x "
          f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.param_dtype}; device {dev[0].device_kind} x {len(dev)}; "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg))
    print(f"params: {sum(x.size for x in jax.tree.leaves(params)):,} "
          f"random from seed {args.seed} in "
          f"{time.perf_counter() - t0:.1f}s")
    arrivals = traffic.poisson_arrivals(
        N_REQUESTS, RATE, seed=args.seed, vocab=cfg.vocab,
        min_prompt=PROMPT[0], max_prompt=PROMPT[1], min_output=MAX_NEW,
        max_output=MAX_NEW,
        scenarios=(traffic.Scenario("smoke", 1.0, 1.0,
                                    shared_prefixes=PREFIX[0],
                                    prefix_tokens=PREFIX[1]),))
    print(f"requests: {len(arrivals)} Poisson arrivals over ticks "
          f"[{arrivals[0].arrival_tick}, {arrivals[-1].arrival_tick}], "
          f"prompts {[a.prompt_len for a in arrivals]} tokens, {MAX_NEW} "
          f"new tokens each; max_len {MAX_LEN}, {SLOTS} slots, chunk "
          f"{CHUNK}, seq_tile {SEQ_TILE}")

    if args.chips == 1:
        eng = build_engine(params, cfg)
        got = serve("compiled", eng, arrivals, meter)
        if not decode_has_kernel(eng, cfg):
            fail("the compiled decode program holds no tpu_custom_call")
        print("compiled decode program holds tpu_custom_call: True")
        want = serve("reference", build_engine(params, cfg,
                                               kernel_mode="reference"),
                     arrivals, meter)
        compare("compiled vs reference", got, want, arrivals, params, cfg)
    else:
        mesh = make_kv_mesh(4, devices=dev[:4])
        got = serve("sharded x4", build_engine(params, cfg, mesh=mesh),
                    arrivals, meter)
        want = serve("unsharded", build_engine(params, cfg), arrivals, meter)
        compare("sharded vs unsharded", got, want, arrivals, params, cfg)

    stats = dev[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on "
          f"{dev[0]}; compile {meter.seconds:.1f}s total, "
          f"{meter.hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))


if __name__ == "__main__":
    main()
