"""Public jit'd wrappers around the Pallas kernels.

Each wrapper owns the request preprocessing (write dedup, masking) so the
kernel bodies stay pure data movement + matmul, and exposes an ``interpret``
flag whose default (None) follows the backend — see
``tiling.resolve_interpret``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.multiport import MemorySpec, _dedup_last_wins
from repro.core.ports import MAX_PORTS, READ, WRITE, PortConfig, PortRequest
from repro.kernels import flash_attention as fa
from repro.kernels import kv_multiport as kvmp
from repro.kernels import kv_prefill_chunk as kvpc
from repro.kernels import multiport_sram as mps


def multiport_step(spec: MemorySpec, config: PortConfig, storage: jax.Array,
                   requests: Sequence[PortRequest], *,
                   interpret: bool | None = None
                   ) -> tuple[jax.Array, list[jax.Array]]:
    """Kernel-backed macro-cycle with the same contract as core.multiport.step.

    Only the ENABLED ports' queues are packed and shipped to the kernel (in
    service order), so disabled ports cost zero HBM traffic — the C1 property
    at the request-metadata level: storage traversal bytes are constant in the
    port count, and queue bytes scale only with the ports actually enabled.
    """
    q = requests[0].queue_len
    for r in requests:
        if r.queue_len != q:
            raise ValueError("all port queues must share one queue length")

    order = config.service_order()                    # enabled, priority order
    addrs, datas = [], []
    for p in order:
        r = requests[p]
        m = r.mask
        if config.roles[p] == WRITE:
            m = _dedup_last_wins(r.addr, m)          # last-wins in queue order
        # OOB and masked lanes carry the no-word address -1
        m = m & (r.addr >= 0) & (r.addr < spec.num_words)
        addrs.append(jnp.where(m, r.addr, -1).astype(jnp.int32))
        datas.append(r.data.astype(spec.dtype))

    banked = storage.reshape(spec.num_banks, spec.words_per_bank,
                             spec.word_width)
    banked, packed = mps.multiport_sram_step(
        banked, jnp.stack(addrs), jnp.stack(datas),
        roles=tuple(config.roles[p] for p in order), interpret=interpret)
    reads = [jnp.zeros((q, spec.word_width), spec.dtype)
             for _ in range(MAX_PORTS)]
    for slot, p in enumerate(order):
        if config.roles[p] == READ:
            reads[p] = packed[slot]
    return banked.reshape(spec.num_words, spec.word_width), reads


def _kv_shard_wrap(kernel, mesh, mesh_axis: str, batch: int, n_in: int,
                   n_out: int):
    """Wrap a fused KV kernel launch in ``shard_map`` over the batch axis of
    every operand: each device services ITS sequences with its own SMEM
    scalar prefetch (the shard's cache_len/offset/chunk_len slice) and its
    own dynamic live-tile bound — ``jnp.max`` over the shard-local lengths
    inside the mapped body — so a device holding short sequences traverses
    fewer tiles than one holding long sequences. Returns the kernel
    unchanged when the mesh is absent or trivial."""
    if mesh is None:
        return kernel
    from jax.sharding import PartitionSpec as P
    n = int(mesh.shape[mesh_axis])
    if n == 1:
        return kernel
    if batch % n:
        raise ValueError(
            f"kv-sharded kernel launch needs the batch ({batch}) to divide "
            f"across the {n}-way {mesh_axis!r} axis — pad the staged batch "
            f"to a whole number of rows per device")
    return jax.shard_map(kernel, mesh=mesh, in_specs=(P(mesh_axis),) * n_in,
                         out_specs=(P(mesh_axis),) * n_out, check_vma=False)


@functools.partial(jax.jit, static_argnames=("seq_tile", "live_len",
                                             "length_mask", "dynamic_grid",
                                             "num_kv_splits", "interpret",
                                             "mesh", "mesh_axis", "port_mix"))
def fused_decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                           new_k: jax.Array, new_v: jax.Array,
                           cache_len: jax.Array, *, seq_tile: int = 128,
                           live_len: int | None = None,
                           length_mask: bool = True,
                           dynamic_grid: bool = False,
                           num_kv_splits: int = 1,
                           interpret: bool | None = None,
                           mesh=None, mesh_axis: str = "kv",
                           port_mix: str = "wr"):
    """Scheduled-port-mix decode step. See kv_multiport.py.

    ``port_mix`` is the compute-side port-mix decision made by the engine's
    macro-cycle scheduler: ``"wr"`` (a 1W+1R traversal is schedulable) runs
    the fused append+attend kernel — ONE length-bounded VMEM traversal
    services both ports with same-cycle W->R visibility; ``"w+r"`` (port
    budget of 1: the W and R ports cannot share a traversal) degrades to
    the two-pass oracle — append traversal then dense attend traversal
    (``mesh``/masking flags are fused-path concerns and are ignored there).

    ``dynamic_grid=True`` bounds the traversal with the runtime live-tile
    count instead of the static ``live_len`` prefix — one trace serves every
    cache length. ``num_kv_splits > 1`` runs the two-stage split-KV path
    (grid-parallel partial attention + LSE combine; 1 is the serial
    bit-exact oracle) — the ``"w+r"`` two-pass oracle has no traversal to
    split and ignores it. ``mesh`` (with a ``mesh_axis`` axis) runs the
    traversal under ``shard_map`` over the batch axis: per-shard SMEM
    scalars, per-shard live-tile bounds (see ``_kv_shard_wrap``); both
    split stages live inside the wrapped launch, so per-shard split bounds
    come from the shard-local lengths for free."""
    if port_mix == "w+r":
        from repro.kernels import ref
        return ref.decode_attention_ref(q, cache_k, cache_v, new_k, new_v,
                                        cache_len)
    if port_mix != "wr":
        raise ValueError(f"unknown port_mix: {port_mix!r}")
    kernel = functools.partial(kvmp.fused_append_attend, seq_tile=seq_tile,
                               live_len=live_len, length_mask=length_mask,
                               dynamic_grid=dynamic_grid,
                               num_kv_splits=num_kv_splits,
                               interpret=interpret)
    kernel = _kv_shard_wrap(kernel, mesh, mesh_axis, q.shape[0],
                            n_in=6, n_out=3)
    return kernel(q, cache_k, cache_v, new_k, new_v, cache_len)


@functools.partial(jax.jit, static_argnames=("seq_tile", "live_len",
                                             "dynamic_grid", "interpret",
                                             "mesh", "mesh_axis", "port_mix"))
def fused_prefill_chunk_attention(q: jax.Array, cache_k: jax.Array,
                                  cache_v: jax.Array, new_k: jax.Array,
                                  new_v: jax.Array, offset: jax.Array,
                                  chunk_len: jax.Array, *,
                                  seq_tile: int = 128,
                                  live_len: int | None = None,
                                  dynamic_grid: bool = False,
                                  interpret: bool | None = None,
                                  mesh=None, mesh_axis: str = "kv",
                                  port_mix: str = "wr"):
    """Scheduled-port-mix chunked-prefill step.

    See kv_prefill_chunk.py; like the decode wrapper, ``port_mix="wr"``
    runs the fused 1W+1R length-bounded traversal and ``"w+r"`` (1-port
    budget) degrades to the two-pass oracle
    ``ref.prefill_chunk_attention_ref`` — scatter traversal then dense
    attend traversal.
    ``dynamic_grid=True`` bounds the traversal with the runtime live-tile
    count instead of the static ``live_len`` prefix. ``mesh`` shards the
    traversal over the batch axis exactly like the decode wrapper."""
    if port_mix == "w+r":
        from repro.kernels import ref
        return ref.prefill_chunk_attention_ref(q, cache_k, cache_v, new_k,
                                               new_v, offset, chunk_len)
    if port_mix != "wr":
        raise ValueError(f"unknown port_mix: {port_mix!r}")
    kernel = functools.partial(kvpc.fused_chunk_append_attend,
                               seq_tile=seq_tile, live_len=live_len,
                               dynamic_grid=dynamic_grid, interpret=interpret)
    kernel = _kv_shard_wrap(kernel, mesh, mesh_axis, q.shape[0],
                            n_in=7, n_out=3)
    return kernel(q, cache_k, cache_v, new_k, new_v, offset, chunk_len)


@functools.partial(jax.jit, static_argnames=("causal", "q_tile", "k_tile", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_tile: int = 128, k_tile: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    return fa.flash_attention(q, k, v, causal=causal, q_tile=q_tile,
                              k_tile=k_tile, interpret=interpret)
