"""Pallas TPU kernel: banked N-port memory step — one traversal, N ports.

This is the paper's wrapper realized at the HBM<->VMEM boundary. The storage
is banked ``[num_banks, words_per_bank, W]``; the grid walks banks; each grid
step stages ONE bank tile in VMEM and services every enabled port's traffic to
that bank, in priority order (the FSM walk unrolled — at most 4 slots).

The caller packs ONLY the enabled ports, already in service order (see
ops.multiport_step): disabled ports contribute zero DMA traffic and zero
compute, so the kernel's HBM footprint is storage + (enabled-port queues).

TPU adaptation notes (DESIGN.md §2):
  * gather/scatter are realized as one-hot matmuls — MXU-friendly and free of
    dynamic-index hazards (a 65nm address decoder becomes a one-hot row; the
    sense amplifier becomes a [Q, wpb] x [wpb, W] matmul).
  * the bandwidth claim C1 falls out structurally: the baseline macro makes one
    full HBM traversal per enabled port; this kernel makes exactly one
    traversal regardless of the enabled-port count.
  * BlockSpec tiling: the grid is (word-column tiles, banks). Every
    transaction moves whole words, so column tiles are independent and a
    step holds a ``[wpb, ct]`` bank tile plus the ``[P_eff, Q, ct]`` payload
    and read blocks; ``ct`` is the widest 128-lane column tile whose
    working set fits :data:`~repro.kernels.tiling.VMEM_LIMIT_BYTES`, and
    :func:`bank_count` sizes banks from a per-bank byte budget, so a
    full-width word (tinyllama-1.1b: 44 KiB) still tiles into VMEM.

Priority semantics (claim C3) hold per bank; banks partition the address
space, so cross-bank ordering is immaterial.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ports import WRITE
from repro.kernels.tiling import LANE, VMEM_LIMIT_BYTES, resolve_interpret

# Bytes of one bank (words_per_bank full words): small enough that the
# one-hot decode matrix [Q, wpb] stays a minor share of a step's VMEM.
BANK_BYTES = 2 << 20


def bank_count(num_words: int, word_bytes: int) -> int:
    """Fewest power-of-two banks, each dividing ``num_words``, whose bank of
    ``num_words // banks`` words fits ``BANK_BYTES`` (or the most banks
    ``num_words`` divides into, when no count fits)."""
    nb = 1
    while ((num_words // nb) * word_bytes > BANK_BYTES
           and num_words % (2 * nb) == 0):
        nb *= 2
    return nb


def _col_tile(w: int, wpb: int, p_eff: int, q: int, itemsize: int) -> int:
    """Widest column tile (a 128-lane multiple dividing ``w``) whose step
    working set — the double-buffered bank tile in and out, payload and
    read blocks, and the one-hot matrix — fits half the VMEM limit. Word
    widths under a lane ride whole."""
    if w % LANE:
        return w
    best = LANE
    for ct in range(LANE, w + 1, LANE):
        need = (4 * wpb * ct + 4 * p_eff * q * ct + wpb * q) * itemsize
        if w % ct == 0 and need <= VMEM_LIMIT_BYTES // 2:
            best = ct
    return best


def _kernel(addr_ref, data_ref, storage_ref, out_storage_ref, reads_ref, *,
            roles: tuple[int, ...], words_per_bank: int):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        reads_ref[...] = jnp.zeros_like(reads_ref)

    tile = storage_ref[0]                                   # [wpb, ct]
    dtype = tile.dtype
    wpb = words_per_bank
    q = addr_ref.shape[2]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (wpb, q), 0)
    hi = jax.lax.Precision.HIGHEST                          # exact word moves

    for slot, role in enumerate(roles):                     # FSM walk, unrolled
        # one-hot address decode, transposed: sel_t[w, q] == lane q targets
        # word w of this bank (masked lanes carry address -1: no word)
        sel_t = (addr_ref[slot] - b * wpb == row_ids).astype(dtype)
        if role == WRITE:
            written = sel_t.max(axis=1, keepdims=True) > 0  # [wpb, 1]
            newvals = jax.lax.dot(sel_t, data_ref[slot], precision=hi,
                                  preferred_element_type=dtype)
            tile = jnp.where(written, newvals, tile)
        else:
            got = jax.lax.dot_general(sel_t, tile, (((0,), (0,)), ((), ())),
                                      precision=hi,
                                      preferred_element_type=dtype)
            reads_ref[slot] = reads_ref[slot] + got

    out_storage_ref[0] = tile


def multiport_sram_step(storage_banked: jax.Array, addr: jax.Array,
                        data: jax.Array, *, roles: tuple[int, ...],
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """One macro-cycle over banked storage.

    Args:
      storage_banked: [num_banks, words_per_bank, W].
      addr: int32 [P_eff, Q] word addresses for the ENABLED ports only,
            stacked in service (priority) order; -1 marks a masked lane.
            Write lanes must already be deduped (last-wins) by the caller
            — see ops.multiport_step.
      data: [P_eff, Q, W] write payloads (same order).
      roles: READ/WRITE per packed slot, in service order (jit
            specialization key).

    Returns:
      (storage_banked', reads[P_eff, Q, W]) — reads are zeros for write slots.
    """
    nb, wpb, w = storage_banked.shape
    p_eff, q = addr.shape
    assert p_eff == len(roles)
    ct = _col_tile(w, wpb, p_eff, q, storage_banked.dtype.itemsize)

    kernel = functools.partial(_kernel, roles=tuple(roles), words_per_bank=wpb)
    out_storage, reads = pl.pallas_call(
        kernel,
        grid=(w // ct, nb),
        name="multiport_pool_step",
        in_specs=[
            pl.BlockSpec((p_eff, 1, q), lambda j, b: (0, 0, 0)),   # addr rows
            pl.BlockSpec((p_eff, q, ct), lambda j, b: (0, 0, j)),  # data
            pl.BlockSpec((1, wpb, ct), lambda j, b: (b, 0, j)),    # storage
        ],
        out_specs=[
            pl.BlockSpec((1, wpb, ct), lambda j, b: (b, 0, j)),    # storage out
            pl.BlockSpec((p_eff, q, ct), lambda j, b: (0, 0, j)),  # reads
        ],
        out_shape=[
            jax.ShapeDtypeStruct(storage_banked.shape, storage_banked.dtype),
            jax.ShapeDtypeStruct((p_eff, q, w), storage_banked.dtype),
        ],
        input_output_aliases={2: 0},                           # storage in-place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
    )(addr.reshape(p_eff, 1, q), data, storage_banked)
    return out_storage, reads
