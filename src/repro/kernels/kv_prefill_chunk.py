"""Pallas TPU kernel: fused chunked-prefill append+attend over the multi-port
KV cache — the length-bounded traversal for the PREFILL port.

The chunked-prefill analogue of ``kv_multiport.fused_append_attend``: one
mid-prefill macro-cycle conventionally pays a scatter pass (write the chunk's
K,V at ``[offset, offset+chunk_len)``) plus a DENSE read of the entire
``S_max`` staging cache for the chunk's attention. This kernel configures the
cache as a 2-port (1W+1R) memory and services both ports in one length-
bounded traversal:

  W port (priority A): each cache tile takes the chunk rows whose destination
      ``offset + row`` lands inside it (routed by a one-hot matmul so the
      scatter lowers through the MXU, no gather needed);
  R port (priority B): every LIVE tile feeds the chunk's online-softmax
      attention — same-cycle W->R visibility, so queries see their own and
      earlier rows of the just-written chunk.

Geometry is Mosaic-ready: the cache rides in WORD layout ``[B, Sp, hkv*Dp]``
(tiles ``[seq_tile, word]``, minor dim lane-padded via ``word_pad``, per-head
columns on lane boundaries), the q/out blocks are head-major rows
``[1, H * Cp, Dp]`` so every intermediate is a 2-D ``[G * Cp, T]`` or
``[G * Cp, Dp]`` block per kv head (rank-3 ``[C, H, T]`` intermediates
overflow VMEM at full width), and the per-sequence offset / chunk-length
scalars ride in SMEM via scalar prefetch.

Length bounding is the point: only tiles ``[0, ceil((offset+chunk_len) /
seq_tile))`` are serviced — tiles wholly past a sequence's last query
position skip the W/R service under ``pl.when`` and copy their cache block
through unchanged (every LAUNCHED output block is written on every grid
step, so the kernel is safe under compiled Mosaic's output-revolving
buffers) — per-chunk read traffic scales with the LIVE sequence length, not
the allocated ``S_max``. A sentinel ``offset = -1`` marks a dead (padded)
batch row: no tile is serviced for it at all. Callers additionally bound
the outer grid either statically (``live_len`` prefix slicing — the
bucketed fallback) or dynamically (``dynamic_grid=True``: the grid bound is
the runtime live-tile count from the prefetched scalars, so one trace
services every live length).

Grid: (batch, live_tiles); per-row accumulators in VMEM scratch persist
across the inner dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (LANE, SUBLANE, VMEM_LIMIT_BYTES,
                                  clamp_seq_tile, live_tile_bound,
                                  pack_words, pad_dim, resolve_interpret,
                                  restore_live, slice_live, unpack_words,
                                  word_pad)


def _kernel(off_ref, clen_ref, q_ref, k_ref, v_ref, new_k_ref, new_v_ref,
            out_k_ref, out_v_ref, o_ref, t_ref, m_scr, l_scr, acc_scr,
            n_scr, *, seq_tile: int, hkv: int, g: int, dp: int, cp: int,
            scale: float):
    bb = pl.program_id(0)
    t = pl.program_id(1)
    n_tiles = pl.num_programs(1)          # static OR the dynamic live bound
    rows = g * cp                         # query rows per kv head

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    off = off_ref[bb]                                     # SMEM scalars
    cl = clen_ref[bb]
    tile_start = t * seq_tile
    # last position any query row attends to: padded rows (row >= chunk_len)
    # replicate position ``offset``, live rows reach offset + chunk_len - 1;
    # a dead batch row (offset < 0) has no live tile at all
    qpos_max = off + jnp.maximum(cl - 1, 0)
    touched = (tile_start <= qpos_max) & (off >= 0)

    @pl.when(touched)
    def _service():
        n_scr[...] += 1                                   # serviced-tile count
        f32 = jnp.float32
        # --- W port (priority A): land the chunk rows that map to this tile.
        # One-hot routing matrix [T, Cp] -> the whole-word scatter is one
        # MXU matmul against the packed [Cp, word] chunk (exact: HIGHEST
        # keeps every f32 bit of the routed word). Masks are built 2-D from
        # iotas: Mosaic cannot reshape a 1-D bool vector into a column.
        rel = (tile_start - off
               + jax.lax.broadcasted_iota(jnp.int32, (seq_tile, cp), 0))
        roww = jax.lax.broadcasted_iota(jnp.int32, (seq_tile, cp), 1)
        route = ((rel == roww) & (roww < cl)).astype(f32)  # [T, Cp]
        w_hit = (rel[:, :1] >= 0) & (rel[:, :1] < cl)      # [T, 1]
        hi = jax.lax.Precision.HIGHEST
        k_new = jax.lax.dot(route, new_k_ref[0].astype(f32), precision=hi,
                            preferred_element_type=f32)   # [T, word]
        v_new = jax.lax.dot(route, new_v_ref[0].astype(f32), precision=hi,
                            preferred_element_type=f32)
        k_tile = jnp.where(w_hit, k_new.astype(k_ref.dtype), k_ref[0])
        v_tile = jnp.where(w_hit, v_new.astype(v_ref.dtype), v_ref[0])
        out_k_ref[0] = k_tile                             # aliased write-thru
        out_v_ref[0] = v_tile

        # --- R port (priority B): causal online-softmax over the live tile,
        # one 2-D [G*Cp, T] score block per kv head (q rows are head-major:
        # row = (head * Cp) + chunk row, see fused_chunk_append_attend)
        row = jax.lax.broadcasted_iota(jnp.int32, (cp, seq_tile), 0)
        pos = tile_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (cp, seq_tile), 1)
        qpos = jnp.where(row < cl, off + row, off)
        valid = pos <= qpos                               # [Cp, T]
        if g > 1:
            valid = jnp.concatenate([valid] * g, axis=0)  # [G*Cp, T]
        for hk in range(hkv):
            r0 = hk * rows
            q = q_ref[0, r0:r0 + rows, :].astype(f32)     # [G*Cp, Dp]
            s = jax.lax.dot_general(
                q, k_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale       # [G*Cp, T]
            s = jnp.where(valid, s, -jnp.inf)

            m_prev = m_scr[r0:r0 + rows, 0]               # [G*Cp]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new),
                              0.0)
            pr = jnp.exp(s - m_new[:, None])
            pr = jnp.where(valid, pr, 0.0)                # [G*Cp, T]
            l_scr[r0:r0 + rows, 0] = (l_scr[r0:r0 + rows, 0] * alpha
                                      + pr.sum(axis=-1))
            pv = jax.lax.dot_general(
                pr, v_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=f32)               # [G*Cp, Dp]
            acc_scr[r0:r0 + rows, :] = (acc_scr[r0:r0 + rows, :]
                                        * alpha[:, None] + pv)
            m_scr[r0:r0 + rows, 0] = m_new

    @pl.when(jnp.logical_not(touched))
    def _pass_through():
        # every LAUNCHED output block is written every grid step (compiled
        # Mosaic recycles output VMEM buffers; an unwritten block would copy
        # back stale data) — the skip saves the W/R service, not the copy
        out_k_ref[0] = k_ref[0]
        out_v_ref[0] = v_ref[0]

    @pl.when(t == n_tiles - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)  # [H*Cp, Dp]
        t_ref[0] = n_scr[...]


def fused_chunk_append_attend(q: jax.Array, cache_k: jax.Array,
                              cache_v: jax.Array, new_k: jax.Array,
                              new_v: jax.Array, offset: jax.Array,
                              chunk_len: jax.Array, *, seq_tile: int = 128,
                              live_len: int | None = None,
                              dynamic_grid: bool = False,
                              return_tiles: bool = False,
                              interpret: bool | None = None
                              ) -> tuple[jax.Array, ...]:
    """One chunked-prefill step for a batch of mid-prefill sequences.

    Args:
      q:         [B, C, H, D] chunk queries (H = Hkv * G); rows past
                 ``chunk_len`` are padding (their outputs are garbage-but-
                 finite, exactly like the jnp oracle).
      cache_k/v: [B, S, Hkv, D] staging caches. S is zero-padded up to a
                 whole tile count before the traversal (and cropped after).
      new_k/v:   [B, C, Hkv, D] the chunk's K,V (rope already applied).
      offset:    [B] int32 — each sequence's cache write offset. A NEGATIVE
                 offset marks a dead (padded) batch row: nothing is written
                 or read for it and its attention output is zeros.
      chunk_len: [B] int32 — valid rows of each sequence's chunk.
      seq_tile:  tile size (capacities that are not tile multiples are
                 padded, keeping the tile aligned).
      live_len:  static bound on the live prefix
                 ``max(offset + max(chunk_len, 1))`` (a row with no chunk
                 rows still attends position ``offset``) — only cache
                 tiles below it are traversed; the suffix
                 ``[live_len, S)`` is returned untouched. Ignored under
                 ``dynamic_grid``.
      dynamic_grid: bound the traversal grid with the RUNTIME live-tile
                 count instead — one trace services every live length.
      return_tiles: also return the KERNEL-MEASURED count of serviced tiles
                 per sequence ([B] int32) — the ground truth the host-side
                 tile accounting is pinned against in tests.

    Returns: (attn_out [B, C, H, D], cache_k', cache_v') plus the
    serviced-tile counts when ``return_tiles``.
    """
    b, s, hkv, d = cache_k.shape
    c = q.shape[1]
    h = q.shape[2]
    assert h % hkv == 0, "GQA requires H % Hkv == 0"
    g = h // hkv
    interpret = resolve_interpret(interpret)

    dp = word_pad(d)
    cp = word_pad(c, SUBLANE)
    wp = hkv * dp
    scale = 1.0 / (d ** 0.5)
    seq_tile = clamp_seq_tile(s, seq_tile)

    ck_w = pack_words(cache_k, seq_tile)                  # [B, Sp, wp]
    cv_w = pack_words(cache_v, seq_tile)
    full_k, full_v = ck_w, cv_w
    if not dynamic_grid:
        live = None if live_len is None else word_pad(live_len, seq_tile)
        ck_w, cv_w, bound = slice_live(ck_w, cv_w, live)
    else:
        bound = ck_w.shape[1]
    grid_tiles = bound // seq_tile

    offs = offset.astype(jnp.int32)
    clens = chunk_len.astype(jnp.int32)
    if dynamic_grid:
        # live bound from the prefetched scalars: dead rows contribute 0;
        # ``last`` is the exclusive end of each row's post-append range
        last = jnp.where(offs >= 0, offs + jnp.maximum(clens - 1, 0) + 1, 0)
        n_tiles = jnp.clip(live_tile_bound(jnp.max(last), seq_tile),
                           1, grid_tiles)
    else:
        n_tiles = grid_tiles

    # head-major query rows: [B, C, H, D] -> [B, H * Cp, Dp] with row
    # h * Cp + c, so each kv head's G query heads form one contiguous
    # [G * Cp, Dp] block and every intermediate in the kernel stays 2-D
    qp = pad_dim(pad_dim(q, 3, dp), 1, cp).transpose(0, 2, 1, 3)
    qp = qp.reshape(b, h * cp, dp)
    nk_w = pad_dim(pad_dim(new_k, 3, dp).reshape(b, c, wp), 1, cp)
    nv_w = pad_dim(pad_dim(new_v, 3, dp).reshape(b, c, wp), 1, cp)

    kernel = functools.partial(_kernel, seq_tile=seq_tile, hkv=hkv, g=g,
                               dp=dp, cp=cp, scale=scale)
    # block SHAPES come from the same geometry table the Mosaic lint test
    # checks (chunk_block_specs) — the lint cannot drift from the launch
    blocks = {nm: blk
              for nm, blk, _ in chunk_block_specs(b, c, bound, h, hkv, d,
                                                  seq_tile)}
    per_b = lambda bb, t, O, C: (bb, 0, 0)        # noqa: E731
    per_tile = lambda bb, t, O, C: (bb, t, 0)     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                            # offs, clens -> SMEM
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec(blocks["q"], per_b),
            pl.BlockSpec(blocks["cache_k"], per_tile),
            pl.BlockSpec(blocks["cache_v"], per_tile),
            pl.BlockSpec(blocks["new_k"], per_b),
            pl.BlockSpec(blocks["new_v"], per_b),
        ],
        out_specs=[
            pl.BlockSpec(blocks["out_k"], per_tile),
            pl.BlockSpec(blocks["out_v"], per_tile),
            pl.BlockSpec(blocks["attn_out"], per_b),
            # serviced-tile counts: one (8, 128) int32 block per row, the
            # counter splatted over it (vector stores only; [:, 0, 0] reads)
            pl.BlockSpec(blocks["tiles"], per_b),
        ],
        scratch_shapes=[
            pltpu.VMEM((h * cp, 1), jnp.float32),         # m
            pltpu.VMEM((h * cp, 1), jnp.float32),         # l
            pltpu.VMEM((h * cp, dp), jnp.float32),        # acc
            pltpu.VMEM((SUBLANE, LANE), jnp.int32),      # serviced tiles
        ],
    )
    out_k, out_v, out, tiles = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="fused_prefill_chunk_attention",
        out_shape=[
            jax.ShapeDtypeStruct(ck_w.shape, ck_w.dtype),
            jax.ShapeDtypeStruct(cv_w.shape, cv_w.dtype),
            jax.ShapeDtypeStruct((b, h * cp, dp), q.dtype),
            jax.ShapeDtypeStruct((b, SUBLANE, LANE), jnp.int32),
        ],
        input_output_aliases={3: 0, 4: 1},                # caches in-place
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(offs, clens, qp, ck_w, cv_w, nk_w, nv_w)

    out_k, out_v = restore_live(full_k, full_v, out_k, out_v)
    out_k = unpack_words(out_k, s, hkv, d)
    out_v = unpack_words(out_v, s, hkv, d)
    out = out.reshape(b, h, cp, dp).transpose(0, 2, 1, 3)[:, :c, :, :d]
    if return_tiles:
        return out, out_k, out_v, tiles[:, 0, 0]
    return out, out_k, out_v


def chunk_block_specs(b: int, c: int, s: int, h: int, hkv: int, d: int,
                      seq_tile: int) -> list[tuple[str, tuple, tuple]]:
    """The chunk kernel's block geometry as (name, block_shape, array_shape)
    triples for the Mosaic geometry-lint test. Every block is rank 3: the
    q/out blocks are head-major ``[1, H * Cp, Dp]`` rows (Cp the chunk
    padded to a sublane multiple), so the kernel's intermediates are 2-D."""
    dp = word_pad(d)
    cp = word_pad(c, SUBLANE)
    wp = hkv * dp
    sp = word_pad(s, seq_tile)
    tile = max(1, min(seq_tile, sp))
    return [
        ("q", (1, h * cp, dp), (b, h * cp, dp)),
        ("cache_k", (1, tile, wp), (b, sp, wp)),
        ("cache_v", (1, tile, wp), (b, sp, wp)),
        ("new_k", (1, cp, wp), (b, cp, wp)),
        ("new_v", (1, cp, wp), (b, cp, wp)),
        ("out_k", (1, tile, wp), (b, sp, wp)),
        ("out_v", (1, tile, wp), (b, sp, wp)),
        ("attn_out", (1, h * cp, dp), (b, h * cp, dp)),
        ("tiles", (1, SUBLANE, LANE), (b, SUBLANE, LANE)),
    ]
