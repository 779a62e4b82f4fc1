"""Set-up that compiles, before the window, every program the window can run.

The engine compiles programs by shape while it serves, and the shapes a
window meets depend on how its requests happen to line up:

- the pool step (``repro.memory.paged_kv._pool_step``): one program per
  port mix and lane bucket (a power of two from 8 up);
- the read path (``PagedPool.cycle``): the read port's result sliced per
  read stream, one program per read length and lane bucket;
- the storage gathers of the prefix cache (``PagedPool.gather_words`` for
  attached pages, the copy-on-write source read): one per word count;
- the prefill program: one per batch bucket of prefilling slots.

A warm-up on the cell's traffic alone reaches these at random, and the
rest then compile inside the window. ``pool_shapes`` compiles the first
three families at every shape the engine's settings allow, without
changing the engine's state; ``prefill_rounds`` makes the requests that
run the prefill program at every batch bucket. Programs compiled once are
read back from the persistent cache by later runs.

The read slices are most of the count, about 0.5-1.3 s each to compile
on a TPU v5e: 519 at ``max_len`` 128 with 8 slots, about 2,300 at
``max_len`` 512, which no run's set-up can afford.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ports import PortConfig, empty_request
from repro.core.priority import complete_priority
from repro.memory import paged_kv

# the ports in the engine's program order: eviction's scrub, prefill's
# bulk fill, then decode's append and read (``MultiPortEngine._build_phases``)
PROGRAM_ORDER = (paged_kv.SCRUB, paged_kv.BULK_FILL, paged_kv.APPEND,
                 paged_kv.ATTN_READ)


def lane_buckets(max_lanes: int) -> list[int]:
    """The pool's lane buckets (``paged_kv._bucket``) up to ``max_lanes``."""
    out = [8]
    while out[-1] < max_lanes:
        out.append(out[-1] * 2)
    return out


def port_mixes() -> list[PortConfig]:
    """Every port mix the engine's scheduler can put in one traversal:
    each non-empty subset of the four ports, its priority the subset in
    program order followed by the rest (``Traversal.priority``)."""
    out = []
    for n in range(1, 5):
        for sub in itertools.combinations(PROGRAM_ORDER, n):
            enabled = tuple(p in sub for p in range(4))
            out.append(PortConfig(enabled=enabled, roles=paged_kv._ROLES,
                                  priority=complete_priority(sub)))
    return out


def pool_shapes(pool, *, max_slots: int, max_len: int, min_read: int,
                prefix_cache: bool, log=None) -> dict:
    """Compile the pool's programs at every shape a window of this engine
    can meet; a read is at least ``min_read`` words long (a sequence is
    read once its prompt is in). Every request lane is masked, so nothing
    is written, and the
    pool step's result is dropped: the pool keeps its storage. Returns how
    many programs of each family were run and the seconds taken; ``log``,
    if given, is called with a progress line after each lane bucket."""
    t0 = time.perf_counter()
    spec = pool.spec
    n = {"pool_step": 0, "read_slice": 0, "gather": 0}
    for q in lane_buckets(max_slots * max_len):
        reqs = tuple(empty_request(q, spec.word_width, spec.dtype)
                     for _ in range(4))
        read = None
        for cfg in port_mixes():
            _, out = paged_kv._pool_step(spec, cfg, pool.storage, reqs,
                                         use_kernel=pool.use_kernel,
                                         interpret=pool.interpret)
            n["pool_step"] += 1
            if cfg.enabled[paged_kv.ATTN_READ]:
                read = out[paged_kv.ATTN_READ]
        for length in range(min_read, min(q, max_len) + 1):
            read[0:length, :pool.io_width]
            n["read_slice"] += 1
        jax.block_until_ready(read)
        if log is not None:
            log(f"lane bucket {q}: {n} in {time.perf_counter() - t0:.1f}s")
    if prefix_cache:
        # attached prefixes come in whole pages; a copy-on-write source is
        # the live words of one partly filled page
        pt = pool.page_tokens
        for k in list(range(1, pt)) + list(range(pt, max_len + 1, pt)):
            np.asarray(pool.storage[jnp.asarray(np.zeros(k, np.int32))])
            n["gather"] += 1
    n["seconds"] = time.perf_counter() - t0
    return n


@dataclasses.dataclass(frozen=True)
class Request:
    """A set-up request, as the driver offers it."""
    due_s: float
    prompt: tuple
    max_new: int
    phase: str = "shapes"


def prefill_rounds(*, max_slots: int, chunk: int, vocab: int,
                   seed: int) -> list[list[Request]]:
    """Rounds of one-chunk requests, each round due once the one before
    has finished: ``max_slots`` of them, then half as many, and so on
    down to one, so that the prefill program runs at every batch bucket.
    Each asks for 2 tokens, so that it decodes once too."""
    rng = np.random.default_rng([seed, 0x5eed])
    rounds, n = [], max_slots
    while n >= 1:
        rounds.append([Request(0.0, tuple(int(t) for t in
                                          rng.integers(0, vocab, chunk)), 2)
                       for _ in range(n)])
        n //= 2
    return rounds
