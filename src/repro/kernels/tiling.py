"""Shared tiling + Mosaic-geometry helpers for the length-bounded KV-cache
kernels (`kv_multiport` decode, `kv_prefill_chunk` chunked prefill).

Both kernels traverse the cache in ``seq_tile``-sized tiles. Two geometry
disciplines live here:

* **(8, 128)/f32 alignment.** Compiled Mosaic tiles the last two dims of
  every block as (SUBLANE, LANE) = (8, 128) for f32. The kernels therefore
  operate on a WORD layout: a cache tile is ``[seq_tile, word]`` where the
  word packs every KV head's vector padded to the lane width
  (``word = hkv * word_pad(head_dim)``), so the minor dim is always a
  128-multiple and per-head slices land on lane boundaries. ``word_pad``
  rounds CI's small head dims (8/16 words) up to a full lane — small word
  widths still run, they just ride zero lanes that are cropped on the way
  out. ``pack_words`` / ``unpack_words`` are the (bit-exact) pad+flatten /
  crop round trip.

* **Live-prefix bounding.** The wrapper either slices the caches to a static
  ``live_len`` prefix before launching (the bucketed path — one retrace per
  ladder entry) or leaves the capacity alone and bounds the GRID itself with
  a scalar live-tile count (the dynamic-grid path — one trace for every
  cache length; see the kernel modules).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

# Mosaic f32 tile: (sublane, lane) minor-dims minimum.
LANE = 128
SUBLANE = 8

# Scoped VMEM a kernel may claim: half of a TPU v5e core's 128 MiB. The
# compiler's default scope (16 MiB) refuses the chunked-prefill kernel at
# 256-token chunks of tinyllama-1.1b; the other half stays free for the
# compiler's own buffers.
VMEM_LIMIT_BYTES = 64 << 20

_fit_warned: set = set()


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place the Pallas execution mode is chosen. ``None`` — the
    default of every ``interpret`` parameter in this package — follows the
    backend arrays live on: kernel bodies run in the Pallas interpreter on
    CPU and compile through Mosaic on a TPU. An explicit bool wins (a test
    compiling for a described, unattached chip passes False)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def word_pad(n: int, unit: int = LANE) -> int:
    """Round a minor (lane) dim up to the Mosaic tile unit."""
    return -(-int(n) // unit) * unit


def live_tile_bound(last_exclusive, seq_tile: int):
    """Tiles covering positions ``[0, last_exclusive)`` — the ONE live-tile
    bound formula shared by the decode, chunked-prefill and split-KV
    traversals.

    ``last_exclusive`` is always the EXCLUSIVE end of the live range: the
    decode kernel passes ``max(cache_len) + 1`` (the append position is
    live after the in-traversal write), the chunk kernel passes
    ``max(offset + chunk_len)``, and the split-KV partial-attention path
    passes each row's own post-append length. The two kernels used to
    inline algebraically-equal but textually-different forms of this
    ceil-div (inclusive ``(last + tile) // tile`` vs exclusive
    ``(last + tile - 1) // tile``) — exactly how a future edit breaks one
    silently. Accepts ints and traced jnp scalars alike; callers clip the
    result to their grid capacity (and to >= 1 for all-dead batches)."""
    return (last_exclusive + seq_tile - 1) // seq_tile


def clamp_seq_tile(s: int, seq_tile: int) -> int:
    """The kernels' launch-time tile clamp ``max(1, min(seq_tile, s))`` —
    no longer silent. A configured tile larger than the traversed capacity
    diverges from what the launcher validated against the engine's
    ``final_stage_ladder`` (and from the host-side tile accounting), so the
    first time a given ``(s, seq_tile)`` pair clamps DOWN, a warning names
    both sizes through the same once-per-geometry machinery as
    :func:`fit_seq_tile`."""
    t = max(1, min(seq_tile, s))
    if t != seq_tile:
        key = ("clamp", s, seq_tile)
        if key not in _fit_warned:
            _fit_warned.add(key)
            warnings.warn(
                f"seq_tile {seq_tile} exceeds the traversed capacity {s}; "
                f"clamping to {t} — the launch geometry no longer matches "
                f"the validated --seq-tile (validate against "
                f"final_stage_ladder, or pass seq_tile <= capacity)",
                stacklevel=2)
    return t


def fit_seq_tile(s: int, seq_tile: int) -> int:
    """Largest divisor of ``s`` that is <= ``seq_tile``, preferring
    SUBLANE-aligned divisors (Mosaic sublane geometry) over raw size.

    The serving engine never relies on this fallback — its staging buckets
    are whole tile counts — but direct callers with awkward capacities
    degrade gracefully instead of crashing on a divisibility assert. The
    degradation is no longer silent: the first time a given (s, seq_tile)
    pair clamps, a warning names the fallback tile (a prime capacity
    degrades all the way to tile 1 — pad the capacity instead)."""
    t = max(1, min(seq_tile, s))
    if s % t == 0:
        return t
    divisors = [d for d in range(t, 0, -1) if s % d == 0]
    aligned = [d for d in divisors if d % SUBLANE == 0]
    pick = aligned[0] if aligned else divisors[0]
    key = (s, seq_tile)
    if key not in _fit_warned:
        _fit_warned.add(key)
        warnings.warn(
            f"seq_tile {seq_tile} does not divide capacity {s}; clamping to "
            f"the largest {'aligned ' if aligned else ''}divisor {pick}"
            + ("" if aligned else
               f" (not a multiple of {SUBLANE}: interpret-only geometry —"
               f" pad the capacity to a tile multiple instead)"),
            stacklevel=2)
    return pick


def iota(n: int, dtype=jnp.int32) -> jax.Array:
    """1-D iota via the TPU-legal 2-D broadcasted form."""
    return jax.lax.broadcasted_iota(dtype, (n, 1), 0)[:, 0]


def pad_dim(x: jax.Array, axis: int, target: int) -> jax.Array:
    """Zero-pad one axis of ``x`` up to ``target`` (no-op when equal)."""
    n = x.shape[axis]
    if n == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads)


def pack_words(cache: jax.Array, seq_tile: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, Sp, Hkv * Dp] word layout.

    Each head's D vector is zero-padded to a whole lane count
    (``Dp = word_pad(D)``) so per-head column slices are lane-aligned, and
    the sequence dim is zero-padded to a whole tile count
    (``Sp = ceil(S / seq_tile) * seq_tile``) so the grid never needs a
    degenerate fit-down tile. Exact inverse: :func:`unpack_words`."""
    b, s, hkv, d = cache.shape
    dp = word_pad(d)
    sp = word_pad(s, seq_tile)
    cache = pad_dim(pad_dim(cache, 3, dp), 1, sp)
    return cache.reshape(b, sp, hkv * dp)


def unpack_words(words: jax.Array, s: int, hkv: int, d: int) -> jax.Array:
    """[B, Sp, Hkv * Dp] -> [B, S, Hkv, D]: crop the word layout back."""
    b, sp, w = words.shape
    dp = w // hkv
    return words.reshape(b, sp, hkv, dp)[:, :s, :, :d]


def slice_live(cache_k: jax.Array, cache_v: jax.Array,
               live_len: int | None) -> tuple[jax.Array, jax.Array, int]:
    """Bound two [B, S, ...] caches to the static live prefix.

    Returns (k_prefix, v_prefix, bound) where bound == S when live_len is
    None or does not actually shrink the cache."""
    s = cache_k.shape[1]
    bound = s if live_len is None else max(1, min(live_len, s))
    if bound < s:
        return cache_k[:, :bound], cache_v[:, :bound], bound
    return cache_k, cache_v, bound


def restore_live(full_k: jax.Array, full_v: jax.Array, out_k: jax.Array,
                 out_v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Splice computed prefixes back over the full caches (no-op when the
    traversal was unbounded). Rank-agnostic: works on the raw [B, S, Hkv, D]
    caches and on the packed [B, Sp, W] word layout alike."""
    if out_k.shape[1] < full_k.shape[1]:
        zeros = (0,) * full_k.ndim
        out_k = jax.lax.dynamic_update_slice(full_k, out_k, zeros)
        out_v = jax.lax.dynamic_update_slice(full_v, out_v, zeros)
    return out_k, out_v


def check_block(block: tuple, array: tuple) -> list[str]:
    """Mosaic lint for one block spec against its array shape.

    Returns a list of violations (empty == Mosaic-valid): rank must be <= 4
    (5-D blocks do not lower), the minor dim must be a LANE multiple, and
    the second-minor dim must be a SUBLANE multiple or span the full array
    dim (Mosaic's documented alternative)."""
    errs = []
    if len(block) != len(array):
        errs.append(f"block rank {len(block)} != array rank {len(array)}")
        return errs
    if len(block) > 4:
        errs.append(f"rank-{len(block)} block {block}: Mosaic lowers rank<=4")
    if len(block) >= 1 and block[-1] % LANE:
        # full-dim minor blocks only lower cleanly when lane-aligned too;
        # word_pad exists precisely so this never fires for the KV kernels
        errs.append(f"minor dim {block[-1]} of {block}: not a {LANE}-multiple")
    if len(block) >= 2 and block[-2] % SUBLANE and block[-2] != array[-2]:
        errs.append(
            f"second-minor dim {block[-2]} of {block}: not a "
            f"{SUBLANE}-multiple nor the full array dim {array[-2]}")
    return errs
