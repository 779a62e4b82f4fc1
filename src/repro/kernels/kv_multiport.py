"""Pallas TPU kernel: fused decode append+attend over a multi-port KV cache.

The end-to-end carrier of the paper's claim C1 in the serving path. Decoding
one token conventionally costs TWO full traversals of the sequence-length KV
cache tiles:

  pass 1 (write port): scatter-append the new token's K,V at ``cache_len``;
  pass 2 (read port):  gather + attention over positions [0, cache_len].

This kernel configures the cache as a 2-port memory (1W + 1R per the paper's
"any R/W combination") and services both ports in ONE traversal: while each
KV tile is VMEM-resident, the tile containing ``cache_len`` takes the append
(W slot, higher priority) and every tile feeds the online-softmax attention
accumulation (R slot) — W-before-R visibility exactly as the wrapper's FSM
orders same-cycle traffic, so attention sees the just-appended token.

Geometry is Mosaic-ready (the paper's point that an algorithmic multi-port
memory only pays off once its geometry matches the target array):

  * the cache is traversed in WORD layout ``[B, Sp, hkv * Dp]`` (see
    ``tiling.pack_words``): tiles are ``[seq_tile, word]`` with the minor
    dim a 128-lane multiple (``word_pad``) and per-head columns on lane
    boundaries; q/out ride as 3-D ``[B, Hp, Dp]`` blocks (the old rank-5
    ``[1, C, Hkv, G, D]`` shapes do not lower);
  * per-sequence append positions live in SMEM via scalar prefetch
    (``PrefetchScalarGridSpec``), not in a vector block.

The traversal is LENGTH-BOUNDED three ways, so per-token read traffic scales
with the live sequence length instead of the allocated capacity:

  * ``dynamic_grid=True``: the inner grid bound is a RUNTIME scalar — the
    live-tile count ``ceil((max(cache_len) + 1) / seq_tile)`` computed from
    the prefetched lengths — so ONE trace services every cache length
    (``pl.num_programs(1)`` closes the traversal); tiles past the bound are
    never launched and their (aliased) cache blocks stay untouched.
  * ``live_len`` (static) slices the cache to a bucketed live prefix before
    launching — the retrace-per-bucket fallback the engine keeps for
    ``dynamic_grid=False``.
  * per-sequence, tiles wholly past ``cache_len`` skip the W/R service
    under ``pl.when`` (``length_mask=True``) and copy their cache block
    through unchanged (every LAUNCHED output block is written on every grid
    step, so the kernel is safe under compiled Mosaic's output-revolving
    buffers). A skipped tile is exactly a no-op of the online softmax, so
    bounded, bucketed and dynamic-grid traversals agree bit-for-bit.
  * a sentinel ``cache_len = -1`` marks a DEAD batch row (the engine's
    padded slots): no tile is serviced at all and the attention output is
    zeros — so serviced-tile counts stay exact under batch padding.

Grid: (batch, live_tiles); accumulators in VMEM scratch, persisted across
the inner grid dimension.

SPLIT-KV FLASH-DECODE (``num_kv_splits > 1``): the serial R-port walk above
makes one long sequence bound the whole batch's step latency — its live
tiles form a single dependent accumulation chain. The split path breaks the
chain in two stages, the single-device half of sequence-parallel decode:

  * stage 1 partitions each sequence's OWN live range into
    ``num_kv_splits`` contiguous runs of ``ceil(live_tiles / splits)``
    tiles (per-row bounds from the prefetched length, so ragged batches
    split evenly); each run is an independent partial online-softmax
    emitting ``(acc, m, l)`` into per-split outputs laid out on the same
    word geometry (``[B, splits * Hp, Dp]`` acc + ``[B, splits * Hp,
    LANE]`` stats). The W-port append, the ``pl.when`` tile skip and the
    dead-row sentinel all carry over unchanged — the append tile belongs
    to exactly one split, skipped tiles are no-ops of that split's
    softmax, and a dead row leaves every split empty (``m = -inf``).
  * stage 2 is a cheap LSE-combine over the splits (running-max rescale:
    ``acc *= exp(m_old - m_new)``), one program per batch row.

Per-step latency becomes O(live_tiles / splits) + O(splits) instead of
O(live_tiles); serviced-tile counts are IDENTICAL to the serial walk (the
same tiles are touched, just on parallel chains), so the engine's
accounting and the ``--enforce-tile-bound`` gate hold verbatim.
``num_kv_splits=1`` dispatches the serial kernel itself — the bit-exact
oracle the property suite pins the split path against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (LANE, SUBLANE, clamp_seq_tile, iota,
                                  live_tile_bound, pack_words, pad_dim,
                                  resolve_interpret, restore_live,
                                  slice_live, unpack_words, word_pad)


def _kernel(len_ref, q_ref, k_ref, v_ref, new_k_ref, new_v_ref,
            out_k_ref, out_v_ref, o_ref, t_ref, m_scr, l_scr, acc_scr,
            n_scr, *, seq_tile: int, hkv: int, g: int, dp: int,
            scale: float, length_mask: bool):
    bb = pl.program_id(0)
    t = pl.program_id(1)
    n_tiles = pl.num_programs(1)          # static OR the dynamic live bound
    h = hkv * g

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    p = len_ref[bb]                                       # append pos (SMEM)
    tile_start = t * seq_tile
    # length bound: a tile whose first position is past the append slot holds
    # neither the W-port landing site nor any valid R-port position; a dead
    # row (p < 0, batch padding) has no live tile at all
    touched = (tile_start <= p) if length_mask else (p >= 0)

    @pl.when(touched)
    def _service():
        n_scr[...] += 1                                   # serviced-tile count
        f32 = jnp.float32
        pos = tile_start + iota(seq_tile)                 # global positions [T]

        k_tile = k_ref[0]                                 # [T, hkv * Dp]
        v_tile = v_ref[0]

        # --- W slot (priority A): append new token if it lands in this tile -
        hit = (pos == p)                                  # [T]
        k_tile = jnp.where(hit[:, None], new_k_ref[0, 0][None, :], k_tile)
        v_tile = jnp.where(hit[:, None], new_v_ref[0, 0][None, :], v_tile)
        out_k_ref[0] = k_tile                             # write-thru (aliased)
        out_v_ref[0] = v_tile

        # --- R slot (priority B): attention over valid positions (<= p) -----
        # per-kv-head scores on lane-aligned word columns (unrolled over the
        # small static hkv; each slice is a [G, Dp] x [Dp, T] MXU matmul)
        q = q_ref[0].astype(f32)                          # [Hp, Dp]
        dots = (((1,), (1,)), ((), ()))
        s = jnp.concatenate(
            [jax.lax.dot_general(q[hk * g:(hk + 1) * g],
                                 k_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                                 dots, preferred_element_type=f32)
             for hk in range(hkv)], axis=0) * scale       # [H, T]
        valid = (pos <= p)[None, :]                       # new token included
        s = jnp.where(valid, s, -jnp.inf)

        m_prev = m_scr[:, 0]                              # [H]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # guard: fully-masked tile keeps m at -inf; exp(-inf - -inf) -> where
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        pr = jnp.exp(s - m_new[:, None])
        pr = jnp.where(valid, pr, 0.0)                    # [H, T]
        l_scr[:, 0] = l_scr[:, 0] * alpha + pr.sum(axis=-1)
        pv = jnp.concatenate(
            [jax.lax.dot_general(pr[hk * g:(hk + 1) * g],
                                 v_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)
             for hk in range(hkv)], axis=0)               # [H, Dp]
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[:, 0] = m_new

    @pl.when(jnp.logical_not(touched))
    def _pass_through():
        # every LAUNCHED output block is written every grid step: compiled
        # Mosaic recycles output VMEM buffers, so an unwritten block would
        # copy back stale data — the skip saves the W/R service, not the copy
        out_k_ref[0] = k_ref[0]
        out_v_ref[0] = v_ref[0]

    @pl.when(t == n_tiles - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        res = (acc_scr[...] / denom).astype(o_ref.dtype)  # [H, Dp]
        hp = o_ref.shape[1]
        if hp > h:                                        # head-pad rows
            res = jnp.concatenate(
                [res, jnp.zeros((hp - h, dp), o_ref.dtype)], axis=0)
        o_ref[0] = res
        t_ref[0] = n_scr[...]


def _split_kernel(len_ref, q_ref, k_ref, v_ref, new_k_ref, new_v_ref,
                  out_k_ref, out_v_ref, acc_ref, stats_ref, t_ref,
                  m_scr, l_scr, acc_scr, n_scr, *, seq_tile: int, hkv: int,
                  g: int, dp: int, scale: float, length_mask: bool,
                  num_kv_splits: int):
    """Stage 1 of split-KV decode: the serial kernel's W/R service with the
    online-softmax state FANNED OUT over ``num_kv_splits`` independent
    accumulator banks. Tile ``t`` of a row whose post-append live range is
    ``row_tiles`` tiles feeds bank ``t // ceil(row_tiles / splits)`` — a
    per-row contiguous partition, so ragged batches split each row's OWN
    length evenly rather than the batch max. Nothing else moves: the W-port
    append lands in whichever bank owns its tile, skipped tiles pass the
    cache through untouched, and a dead row (``p < 0``) leaves every bank
    at its ``m = -inf`` init. The final grid step spills all banks as
    per-split ``(acc, m, l)`` partials for the combine kernel."""
    bb = pl.program_id(0)
    t = pl.program_id(1)
    n_tiles = pl.num_programs(1)          # static OR the dynamic live bound
    h = hkv * g
    ns = num_kv_splits

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        n_scr[...] = jnp.zeros_like(n_scr)

    p = len_ref[bb]                                       # append pos (SMEM)
    tile_start = t * seq_tile
    touched = (tile_start <= p) if length_mask else (p >= 0)

    # owner bank: per-ROW contiguous split of the row's own live tiles
    row_tiles = live_tile_bound(p + 1, seq_tile)
    per_split = jnp.maximum(live_tile_bound(row_tiles, ns), 1)
    row0 = jnp.clip(t // per_split, 0, ns - 1) * h

    @pl.when(touched)
    def _service():
        n_scr[...] += 1                                   # serviced-tile count
        f32 = jnp.float32
        pos = tile_start + iota(seq_tile)                 # global positions [T]

        k_tile = k_ref[0]                                 # [T, hkv * Dp]
        v_tile = v_ref[0]

        # --- W slot (priority A): append new token if it lands in this tile -
        hit = (pos == p)                                  # [T]
        k_tile = jnp.where(hit[:, None], new_k_ref[0, 0][None, :], k_tile)
        v_tile = jnp.where(hit[:, None], new_v_ref[0, 0][None, :], v_tile)
        out_k_ref[0] = k_tile                             # write-thru (aliased)
        out_v_ref[0] = v_tile

        # --- R slot (priority B): partial softmax into the OWNER bank ------
        q = q_ref[0].astype(f32)                          # [Hp, Dp]
        dots = (((1,), (1,)), ((), ()))
        s = jnp.concatenate(
            [jax.lax.dot_general(q[hk * g:(hk + 1) * g],
                                 k_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                                 dots, preferred_element_type=f32)
             for hk in range(hkv)], axis=0) * scale       # [H, T]
        valid = (pos <= p)[None, :]                       # new token included
        s = jnp.where(valid, s, -jnp.inf)

        m_prev = m_scr[pl.ds(row0, h), 0]                 # [H]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        pr = jnp.exp(s - m_new[:, None])
        pr = jnp.where(valid, pr, 0.0)                    # [H, T]
        l_scr[pl.ds(row0, h), 0] = (l_scr[pl.ds(row0, h), 0] * alpha
                                    + pr.sum(axis=-1))
        pv = jnp.concatenate(
            [jax.lax.dot_general(pr[hk * g:(hk + 1) * g],
                                 v_tile[:, hk * dp:(hk + 1) * dp].astype(f32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)
             for hk in range(hkv)], axis=0)               # [H, Dp]
        acc_scr[pl.ds(row0, h), :] = (acc_scr[pl.ds(row0, h), :]
                                      * alpha[:, None] + pv)
        m_scr[pl.ds(row0, h), 0] = m_new

    @pl.when(jnp.logical_not(touched))
    def _pass_through():
        out_k_ref[0] = k_ref[0]
        out_v_ref[0] = v_ref[0]

    @pl.when(t == n_tiles - 1)
    def _finalize():
        # spill every bank as (acc, m, l) partials on the word geometry:
        # acc [ns*Hp, Dp]; stats [ns*Hp, LANE] with col 0 = m, col 1 = l.
        # Head-pad rows carry m = -inf / l = 0 so the combine sees them as
        # empty, same as a bank no tile ever fed.
        hp = acc_ref.shape[1] // ns
        accs, stats = [], []
        for si in range(ns):
            a = acc_scr[si * h:(si + 1) * h, :]
            m = m_scr[si * h:(si + 1) * h, 0]
            l = l_scr[si * h:(si + 1) * h, 0]
            if hp > h:
                a = jnp.concatenate(
                    [a, jnp.zeros((hp - h, dp), a.dtype)], axis=0)
                m = jnp.concatenate(
                    [m, jnp.full((hp - h,), -jnp.inf, m.dtype)], axis=0)
                l = jnp.concatenate(
                    [l, jnp.zeros((hp - h,), l.dtype)], axis=0)
            accs.append(a)
            stats.append(jnp.concatenate(
                [m[:, None], l[:, None],
                 jnp.zeros((hp, LANE - 2), jnp.float32)], axis=1))
        acc_ref[0] = jnp.concatenate(accs, axis=0)
        stats_ref[0] = jnp.concatenate(stats, axis=0)
        t_ref[0] = n_scr[...]


def _combine_kernel(acc_ref, stats_ref, o_ref, *, num_kv_splits: int):
    """Stage 2 of split-KV decode: LSE-combine the per-split partials with
    the running-max rescale (``acc *= exp(m_old - m_new)``). One program per
    batch row; O(splits) work against stage 1's O(live_tiles / splits). An
    empty split (``m = -inf``) contributes weight 0, and a fully-dead row
    (every split empty) divides 0 by the 1e-30 floor — zeros, exactly the
    serial kernel's dead-row output."""
    hp, dp = o_ref.shape[1], o_ref.shape[2]
    m_run = jnp.full((hp,), -jnp.inf, jnp.float32)
    l_run = jnp.zeros((hp,), jnp.float32)
    a_run = jnp.zeros((hp, dp), jnp.float32)
    for si in range(num_kv_splits):
        m_s = stats_ref[0, si * hp:(si + 1) * hp, 0]
        l_s = stats_ref[0, si * hp:(si + 1) * hp, 1]
        a_s = acc_ref[0, si * hp:(si + 1) * hp, :]
        m_new = jnp.maximum(m_run, m_s)
        # guard: both-empty keeps m at -inf without exp(-inf - -inf) = nan
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - safe), 0.0)
        beta = jnp.where(jnp.isfinite(m_s), jnp.exp(m_s - safe), 0.0)
        a_run = a_run * alpha[:, None] + a_s * beta[:, None]
        l_run = l_run * alpha + l_s * beta
        m_run = m_new
    o_ref[0] = (a_run
                / jnp.maximum(l_run, 1e-30)[:, None]).astype(o_ref.dtype)


def fused_append_attend(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                        new_k: jax.Array, new_v: jax.Array,
                        cache_len: jax.Array, *, seq_tile: int = 128,
                        live_len: int | None = None, length_mask: bool = True,
                        dynamic_grid: bool = False, num_kv_splits: int = 1,
                        return_tiles: bool = False, interpret: bool | None = None
                        ) -> tuple[jax.Array, ...]:
    """One decode step for a batch of sequences.

    Args:
      q:        [B, H, D] query for the new token (H = Hkv * G).
      cache_k:  [B, S, Hkv, D]; cache_v same. S is zero-padded up to a whole
                tile count before the traversal (and cropped after), so
                awkward capacities keep aligned tiles instead of degrading
                the tile size.
      new_k/v:  [B, Hkv, D] the new token's K,V (appended in-kernel).
      cache_len:[B] int32 — current length; the new token is written at this
                position and attended to (post-append length is cache_len+1).
                A NEGATIVE length marks a dead (padded) batch row: nothing
                is written or read for it and its attention output is zeros.
      live_len: static bound on ``max(cache_len) + 1`` — only cache tiles
                below it are traversed; the suffix [live_len, S) is returned
                untouched. Callers bucket it (powers of two of seq_tile) so
                retraces stay logarithmic. Ignored under ``dynamic_grid``.
      length_mask: skip tiles past each sequence's own append position under
                ``pl.when`` (False restores the unbounded traversal — the
                benchmark's comparator).
      dynamic_grid: bound the traversal grid with the RUNTIME live-tile
                count ``ceil((max(cache_len) + 1) / seq_tile)`` instead of a
                static prefix — one trace services every cache length.
                Requires ``length_mask`` (the per-sequence skip is what
                keeps rows shorter than the batch max exact).
      num_kv_splits: > 1 switches to the two-stage split-KV path (see the
                module docstring): stage 1 accumulates each row's live tiles
                into ``num_kv_splits`` independent partial-softmax banks,
                stage 2 LSE-combines them. 1 (the default) launches the
                serial kernel itself — the bit-exact oracle. Serviced-tile
                counts and cache updates are identical either way.
      return_tiles: also return the KERNEL-MEASURED count of serviced tiles
                per sequence ([B] int32) — the ground truth the host-side
                tile accounting is pinned against in tests.

    Returns:
      (attn_out [B, H, D], cache_k', cache_v') — caches updated in place —
      plus the serviced-tile counts when ``return_tiles``.
    """
    b, s, hkv, d = cache_k.shape
    h = q.shape[1]
    assert h % hkv == 0, "GQA requires H % Hkv == 0"
    g = h // hkv
    interpret = resolve_interpret(interpret)
    if dynamic_grid and not length_mask:
        raise ValueError("dynamic_grid requires length_mask=True: rows "
                         "shorter than the batch max rely on the tile skip")

    dp = word_pad(d)
    hp = word_pad(h, SUBLANE)
    wp = hkv * dp
    scale = 1.0 / (d ** 0.5)
    seq_tile = clamp_seq_tile(s, seq_tile)

    # word layout: [B, Sp, hkv * Dp], Sp a whole tile count
    ck_w = pack_words(cache_k, seq_tile)
    cv_w = pack_words(cache_v, seq_tile)
    full_k, full_v = ck_w, cv_w
    if not dynamic_grid:
        live = None if live_len is None else word_pad(live_len, seq_tile)
        ck_w, cv_w, bound = slice_live(ck_w, cv_w, live)
    else:
        bound = ck_w.shape[1]
    grid_tiles = bound // seq_tile

    lens = cache_len.astype(jnp.int32)
    if dynamic_grid:
        # live bound from the scalar lengths: one trace, any cache length;
        # the post-append live range is [0, max(len) + 1) exclusive
        n_tiles = jnp.clip(live_tile_bound(jnp.max(lens) + 1, seq_tile),
                           1, grid_tiles)
    else:
        n_tiles = grid_tiles

    qp = pad_dim(pad_dim(q, 2, dp), 1, hp)                # [B, Hp, Dp]
    nk_w = pad_dim(new_k, 2, dp).reshape(b, 1, wp)        # [B, 1, wp]
    nv_w = pad_dim(new_v, 2, dp).reshape(b, 1, wp)

    ns = max(1, int(num_kv_splits))
    per_b = lambda bb, t, L: (bb, 0, 0)       # noqa: E731 — batch-resident
    per_tile = lambda bb, t, L: (bb, t, 0)    # noqa: E731 — cache traversal
    if ns == 1:
        kernel = functools.partial(_kernel, seq_tile=seq_tile, hkv=hkv, g=g,
                                   dp=dp, scale=scale,
                                   length_mask=length_mask)
        # block SHAPES come from the same geometry table the Mosaic lint test
        # checks (decode_block_specs) — the lint cannot drift from the launch
        blocks = {nm: blk
                  for nm, blk, _ in decode_block_specs(b, bound, h, hkv, d,
                                                       seq_tile)}
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                        # lens -> SMEM
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
                # serviced-tile counts: one (8, 128) int32 block per row,
                # the counter splatted over it (vector stores only)
                pl.BlockSpec(blocks["tiles"], per_b),
            ],
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),        # m
                pltpu.VMEM((h, 1), jnp.float32),        # l
                pltpu.VMEM((h, dp), jnp.float32),       # acc
                pltpu.VMEM((SUBLANE, LANE), jnp.int32),  # serviced tiles
            ],
        )
        out_k, out_v, out, tiles = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            name="fused_decode_attention",
            out_shape=[
                jax.ShapeDtypeStruct(ck_w.shape, ck_w.dtype),
                jax.ShapeDtypeStruct(cv_w.shape, cv_w.dtype),
                jax.ShapeDtypeStruct((b, hp, dp), q.dtype),
                jax.ShapeDtypeStruct((b, SUBLANE, LANE), jnp.int32),
            ],
            input_output_aliases={2: 0, 3: 1},          # caches in-place
            interpret=interpret,
        )(lens, qp, ck_w, cv_w, nk_w, nv_w)
    else:
        # two-stage split-KV: the launch geometry comes from the split
        # extension of the same lint-checked table
        blocks = {nm: blk
                  for nm, blk, _ in split_block_specs(b, bound, h, hkv, d,
                                                      seq_tile, ns)}
        kernel = functools.partial(_split_kernel, seq_tile=seq_tile, hkv=hkv,
                                   g=g, dp=dp, scale=scale,
                                   length_mask=length_mask, num_kv_splits=ns)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                        # lens -> SMEM
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
                pl.BlockSpec(blocks["acc_partial"], per_b),
                pl.BlockSpec(blocks["lse_partial"], per_b),
                pl.BlockSpec(blocks["tiles"], per_b),
            ],
            scratch_shapes=[
                pltpu.VMEM((ns * h, 1), jnp.float32),   # m, per bank
                pltpu.VMEM((ns * h, 1), jnp.float32),   # l, per bank
                pltpu.VMEM((ns * h, dp), jnp.float32),  # acc, per bank
                pltpu.VMEM((SUBLANE, LANE), jnp.int32),  # serviced tiles
            ],
        )
        out_k, out_v, acc, stats, tiles = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            name="fused_decode_attention_split",
            out_shape=[
                jax.ShapeDtypeStruct(ck_w.shape, ck_w.dtype),
                jax.ShapeDtypeStruct(cv_w.shape, cv_w.dtype),
                jax.ShapeDtypeStruct((b, ns * hp, dp), jnp.float32),
                jax.ShapeDtypeStruct((b, ns * hp, LANE), jnp.float32),
                jax.ShapeDtypeStruct((b, SUBLANE, LANE), jnp.int32),
            ],
            input_output_aliases={2: 0, 3: 1},          # caches in-place
            interpret=interpret,
        )(lens, qp, ck_w, cv_w, nk_w, nv_w)
        out = pl.pallas_call(
            functools.partial(_combine_kernel, num_kv_splits=ns),
            grid=(b,),
            name="fused_decode_attention_combine",
            in_specs=[
                pl.BlockSpec(blocks["acc_partial"], lambda bb: (bb, 0, 0)),
                pl.BlockSpec(blocks["lse_partial"], lambda bb: (bb, 0, 0)),
            ],
            out_specs=pl.BlockSpec(blocks["attn_out"], lambda bb: (bb, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((b, hp, dp), q.dtype),
            interpret=interpret,
        )(acc, stats)
    out_k, out_v = restore_live(full_k, full_v, out_k, out_v)
    out_k = unpack_words(out_k, s, hkv, d)
    out_v = unpack_words(out_v, s, hkv, d)
    out = out[:, :h, :d]
    if return_tiles:
        return out, out_k, out_v, tiles[:, 0, 0]
    return out, out_k, out_v


def decode_block_specs(b: int, s: int, h: int, hkv: int, d: int,
                       seq_tile: int) -> list[tuple[str, tuple, tuple]]:
    """The decode kernel's block geometry as (name, block_shape, array_shape)
    triples — the surface the Mosaic geometry-lint test checks across the
    engine's bucket ladder (and the dynamic-grid full-capacity launch)."""
    dp = word_pad(d)
    hp = word_pad(h, SUBLANE)
    wp = hkv * dp
    sp = word_pad(s, seq_tile)
    tile = max(1, min(seq_tile, sp))
    return [
        ("q", (1, hp, dp), (b, hp, dp)),
        ("cache_k", (1, tile, wp), (b, sp, wp)),
        ("cache_v", (1, tile, wp), (b, sp, wp)),
        ("new_k", (1, 1, wp), (b, 1, wp)),
        ("new_v", (1, 1, wp), (b, 1, wp)),
        ("out_k", (1, tile, wp), (b, sp, wp)),
        ("out_v", (1, tile, wp), (b, sp, wp)),
        ("attn_out", (1, hp, dp), (b, hp, dp)),
        ("tiles", (1, SUBLANE, LANE), (b, SUBLANE, LANE)),
    ]


def split_block_specs(b: int, s: int, h: int, hkv: int, d: int,
                      seq_tile: int, num_kv_splits: int
                      ) -> list[tuple[str, tuple, tuple]]:
    """The split-KV launch geometry: the serial decode table plus the
    stage-1 partial outputs / stage-2 inputs. The per-split banks stack on
    the head axis (``num_kv_splits * Hp`` rows), so both extra arrays keep
    a lane-aligned minor dim (Dp for acc, LANE for the (m, l) stats) and a
    SUBLANE-aligned second-minor — same lint surface, one more knob."""
    ns = max(1, int(num_kv_splits))
    dp = word_pad(d)
    hp = word_pad(h, SUBLANE)
    return decode_block_specs(b, s, h, hkv, d, seq_tile) + [
        ("acc_partial", (1, ns * hp, dp), (b, ns * hp, dp)),
        ("lse_partial", (1, ns * hp, LANE), (b, ns * hp, LANE)),
    ]
