"""MultiPortMemory — the paper's wrapper + SRAM macro, adapted to TPU.

Semantics (the contract all kernels/baselines are tested against):

* Storage is a word-addressable array ``[num_words, word_width]`` (the 6T SRAM
  macro). It may be viewed as ``[num_banks, words_per_bank, word_width]`` by
  kernels; banking is an implementation detail invisible to the semantics.
* One ``step`` is one macro-cycle (one external CLK period). Each of the up-to-4
  ports presents a queue of Q word transactions (addr, data, mask).
* Ports are serviced **strictly sequentially in priority order** (contention
  freedom, paper §II-A-3/4): a read port observes every write issued by
  higher-priority ports in the same macro-cycle, and none from lower-priority
  ports. Two write ports hitting the same word resolve to the lower-priority
  (later-serviced) port's value.
* Within one write port's queue, duplicate addresses resolve in queue order
  (last valid lane wins) — the vectorized extension of "one word per internal
  clock" (DESIGN.md §2 delta 1).
* Masked-off lanes issue no transaction; reads of masked lanes return 0.

``step`` below is the executable specification in pure jnp (also the oracle for
the Pallas kernel in ``repro.kernels.multiport_sram``). ``step_banked`` is the
performance path that dispatches to the Pallas kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fsm
from repro.core.ports import (MAX_PORTS, WRITE, PortConfig, PortRequest,
                              empty_request)


@dataclasses.dataclass(frozen=True)
class MemorySpec:
    """Static geometry of the physical macro."""

    num_words: int
    word_width: int
    dtype: jnp.dtype = jnp.float32
    num_banks: int = 8

    def __post_init__(self):
        if self.num_words % self.num_banks:
            raise ValueError("num_words must divide evenly into banks")

    @property
    def words_per_bank(self) -> int:
        return self.num_words // self.num_banks

    def init_storage(self, value: float = 0.0) -> jax.Array:
        return jnp.full((self.num_words, self.word_width), value, self.dtype)

    def nbytes(self) -> int:
        return self.num_words * self.word_width * jnp.dtype(self.dtype).itemsize


def _dedup_last_wins(addr: jax.Array, mask: jax.Array) -> jax.Array:
    """Keep only the last valid occurrence of each address (queue order)."""
    # has_later[i] = exists j > i with addr[j] == addr[i] and mask[j]
    same = (addr[None, :] == addr[:, None]) & mask[None, :]
    later = jnp.triu(same, k=1)                     # j > i
    has_later = later.any(axis=1)
    return mask & ~has_later


def _service_write(storage: jax.Array, req: PortRequest, num_words: int) -> jax.Array:
    eff_mask = _dedup_last_wins(req.addr, req.mask)
    # Out-of-range address == dropped transaction: masked lanes are routed OOB.
    addr_eff = jnp.where(eff_mask, req.addr, num_words)
    return storage.at[addr_eff].set(req.data.astype(storage.dtype), mode="drop")


def _service_read(storage: jax.Array, req: PortRequest, num_words: int) -> jax.Array:
    addr_eff = jnp.where(req.mask, req.addr, num_words)
    out = storage.at[addr_eff].get(mode="fill", fill_value=0)
    return out


def step(spec: MemorySpec, config: PortConfig, storage: jax.Array,
         requests: Sequence[PortRequest]) -> tuple[jax.Array, list[jax.Array]]:
    """One macro-cycle: service all enabled ports in priority order.

    Args:
      spec: memory geometry.
      config: static port configuration.
      storage: ``[num_words, word_width]``.
      requests: MAX_PORTS request bundles (disabled ports' entries ignored).

    Returns:
      (new_storage, reads) where reads[p] is ``[Q, word_width]`` for read
      ports and zeros for write/disabled ports.
    """
    if len(requests) != MAX_PORTS:
        raise ValueError(f"expected {MAX_PORTS} request bundles")
    q = requests[0].queue_len
    reads = [jnp.zeros((q, spec.word_width), spec.dtype) for _ in range(MAX_PORTS)]

    def service(state, port):
        storage, reads = state
        req = requests[port]
        if config.roles[port] == WRITE:
            storage = _service_write(storage, req, spec.num_words)
        else:
            reads = list(reads)
            reads[port] = _service_read(storage, req, spec.num_words)
        return (storage, reads)

    storage, reads = fsm.walk_static(config, (storage, reads), service)
    return storage, list(reads)


def step_banked(spec: MemorySpec, config: PortConfig, storage: jax.Array,
                requests: Sequence[PortRequest], *, interpret: bool | None = None
                ) -> tuple[jax.Array, list[jax.Array]]:
    """Performance path: one physical traversal services all ports (Pallas)."""
    from repro.kernels import ops  # local import: kernels depend on core

    return ops.multiport_step(spec, config, storage, list(requests),
                              interpret=interpret)


def pack_requests(config: PortConfig, queue_len: int, spec: MemorySpec,
                  **per_port: PortRequest) -> list[PortRequest]:
    """Build the MAX_PORTS request list from keyword ports 'a'..'d'."""
    names = "abcd"
    out = []
    for i in range(MAX_PORTS):
        req = per_port.get(names[i])
        if req is None:
            req = empty_request(queue_len, spec.word_width, spec.dtype)
        out.append(req)
    return out


# ---------------------------------------------------------------------------
# Reference simulator (plain Python/numpy) — the ground truth for property
# tests. Deliberately scalar and boring: services ports in priority order,
# lanes in queue order, exactly like the hardware walks internal clock slots.
# ---------------------------------------------------------------------------

def reference_step(spec: MemorySpec, config: PortConfig, storage: np.ndarray,
                   requests: Sequence[PortRequest]) -> tuple[np.ndarray, list[np.ndarray]]:
    storage = np.array(storage, copy=True)
    q = int(np.asarray(requests[0].addr).shape[0])
    reads = [np.zeros((q, spec.word_width), storage.dtype) for _ in range(MAX_PORTS)]
    for port in config.service_order():
        req = requests[port]
        addr = np.asarray(req.addr)
        data = np.asarray(req.data)
        mask = np.asarray(req.mask)
        if config.roles[port] == WRITE:
            for lane in range(q):                      # queue order: last wins
                if mask[lane] and 0 <= addr[lane] < spec.num_words:
                    storage[addr[lane]] = data[lane].astype(storage.dtype)
        else:
            for lane in range(q):
                if mask[lane] and 0 <= addr[lane] < spec.num_words:
                    reads[port][lane] = storage[addr[lane]]
    return storage, reads
