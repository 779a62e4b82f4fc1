"""Paper Table II / Fig. 6 — bandwidth amplification vs enabled port count.

Two measurements per port count N in {1,2,3,4}:
  * storage-traversal bytes per macro-cycle, from the compiled kernel's
    cost_analysis: proposed (one traversal, all ports) vs the bare single-port
    macro (one traversal PER enabled port);
  * port transactions serviced per traversal — the paper's "memory access
    frequency" multiplier (250 MHz CLK -> N x 250 MHz effective).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MemorySpec, PortConfig, READ, WRITE, PortRequest, step
from repro.core.baselines import SinglePortNPass
from repro.kernels import ops

SPEC = MemorySpec(num_words=4096, word_width=128, num_banks=16)
Q = 256
ROLES = (WRITE, READ, READ, WRITE)


def _requests(rng) -> list[PortRequest]:
    out = []
    for _ in range(4):
        out.append(PortRequest(
            addr=jnp.asarray(rng.integers(0, SPEC.num_words, Q), jnp.int32),
            data=jnp.asarray(rng.normal(size=(Q, SPEC.word_width)), jnp.float32),
            mask=jnp.ones((Q,), bool)))
    return out


def _cfg(n: int) -> PortConfig:
    return PortConfig(enabled=tuple(i < n for i in range(4)), roles=ROLES)


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    reqs = _requests(rng)
    storage = jnp.zeros((SPEC.num_words, SPEC.word_width), jnp.float32)
    rows = []
    for n in range(1, 5):
        cfg = _cfg(n)
        # proposed wrapper: one pallas traversal services all N ports
        f = jax.jit(lambda s, r: ops.multiport_step(SPEC, cfg, s, r,
                                                    interpret=True))
        cost = f.lower(storage, reqs).compile().cost_analysis()
        bytes_prop = float(cost.get("bytes accessed", 0.0))

        base = SinglePortNPass(SPEC)
        fb = jax.jit(lambda s, r: base.step(cfg, s, r))
        cost_b = fb.lower(storage, reqs).compile().cost_analysis()
        bytes_base = float(cost_b.get("bytes accessed", 0.0))

        # wall time (CPU; interpret mode for the kernel — relative trend only)
        f(storage, reqs)[0].block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            f(storage, reqs)[0].block_until_ready()
        t_prop = (time.perf_counter() - t0) / 3
        fb(storage, reqs)[0].block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            fb(storage, reqs)[0].block_until_ready()
        t_base = (time.perf_counter() - t0) / 3

        rows.append({
            "ports": n,
            "transactions_per_traversal": n * Q,
            "effective_access_multiplier": n,      # paper: N x 250 MHz
            "proposed_bytes": bytes_prop,
            "baseline_bytes": bytes_base,
            "bytes_ratio_base_over_prop": bytes_base / max(bytes_prop, 1),
            "us_proposed": t_prop * 1e6,
            "us_baseline_npass": t_base * 1e6,
        })
    return rows


def main() -> None:
    rows = run()
    print("# bandwidth amplification (paper Table II, claim C1)")
    print("ports,txn_per_traversal,eff_access_x,prop_bytes,base_bytes,"
          "bytes_ratio,us_prop,us_base")
    for r in rows:
        print(f"{r['ports']},{r['transactions_per_traversal']},"
              f"{r['effective_access_multiplier']},{r['proposed_bytes']:.3g},"
              f"{r['baseline_bytes']:.3g},{r['bytes_ratio_base_over_prop']:.2f},"
              f"{r['us_proposed']:.0f},{r['us_baseline_npass']:.0f}")


if __name__ == "__main__":
    main()
