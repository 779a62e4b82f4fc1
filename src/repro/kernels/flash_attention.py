"""Pallas TPU kernel: causal flash attention (training / prefill hot spot).

Online-softmax tiled attention with GQA support. Grid (B, Hkv, Sq/Tq, Sk/Tk);
running max/denominator/accumulator live in VMEM scratch across the innermost
(key-tile) grid dimension. Key tiles entirely above the causal diagonal are
masked (see perf log in EXPERIMENTS.md §Perf for the tighter variant that
skips them via a tile-level `pl.when` guard, saving the matmuls but not the
tile loads).

Block sizes default to 128x128 (MXU-aligned); d_head up to 256 per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import resolve_interpret


def _iota(n: int, dtype=jnp.int32) -> jax.Array:
    return jax.lax.broadcasted_iota(dtype, (n, 1), 0)[:, 0]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, q_tile: int, k_tile: int, n_k_tiles: int, scale: float,
            causal: bool):
    tq = pl.program_id(2)
    tk = pl.program_id(3)

    @pl.when(tk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        f32 = jnp.float32
        q = q_ref[0, 0].astype(f32)                  # [G, Tq, D]
        k = k_ref[0, 0].astype(f32)                  # [Tk, D]
        v = v_ref[0, 0].astype(f32)                  # [Tk, D]
        s = jnp.einsum("gqd,kd->gqk", q, k) * scale  # [G, Tq, Tk]
        if causal:
            qpos = tq * q_tile + _iota(q_tile)
            kpos = tk * k_tile + _iota(k_tile)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None], s, -jnp.inf)

        m_prev = m_scr[...]                          # [G, Tq]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        acc_scr[...] = (acc_scr[...] * alpha[..., None]
                        + jnp.einsum("gqk,kd->gqd", p, v))
        m_scr[...] = m_new

    if causal:
        # Tiles fully above the diagonal contribute nothing: skip the matmuls.
        pl.when(tq * q_tile + q_tile - 1 >= tk * k_tile)(_compute)
    else:
        _compute()

    @pl.when(tk == n_k_tiles - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_tile: int = 128,
                    k_tile: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Tiled attention.

    Args:
      q: [B, H, Sq, D] (H = Hkv * G); k, v: [B, Hkv, Sk, D].

    Returns: [B, H, Sq, D].
    """
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    q_tile = min(q_tile, sq)
    k_tile = min(k_tile, sk)
    assert sq % q_tile == 0 and sk % k_tile == 0
    n_q, n_k = sq // q_tile, sk // k_tile
    scale = 1.0 / (d ** 0.5)

    qg = q.reshape(b, hkv, g, sq, d)

    kernel = functools.partial(_kernel, q_tile=q_tile, k_tile=k_tile,
                               n_k_tiles=n_k, scale=scale, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, g, q_tile, d), lambda bb, hh, tq, tk: (bb, hh, 0, tq, 0)),
            pl.BlockSpec((1, 1, k_tile, d), lambda bb, hh, tq, tk: (bb, hh, tk, 0)),
            pl.BlockSpec((1, 1, k_tile, d), lambda bb, hh, tq, tk: (bb, hh, tk, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, q_tile, d),
                               lambda bb, hh, tq, tk: (bb, hh, 0, tq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, q_tile), jnp.float32),
            pltpu.VMEM((g, q_tile), jnp.float32),
            pltpu.VMEM((g, q_tile, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qg, k, v)
    return out.reshape(b, h, sq, d)
