"""GQA attention: training (q-chunked causal), prefill (cache write) and
decode (multi-port fused append+attend or two-pass baseline).

The decode path is where the paper's technique lands end-to-end: the KV cache
is a multi-port memory; ``decode_step`` services the write port (append) and
the read port (attend) in one logical traversal. ``kernel_mode`` selects:

  * "reference"  — two-pass jnp (the single-port baseline; always shardable)
  * "multiport"  — the fused Pallas kernel (TPU target; interpret on CPU)
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.models import layers as L


def attention_init(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool = False,
                   dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": L.linear_init(ks[0], d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": L.linear_init(ks[1], d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": L.linear_init(ks[2], d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": L.linear_init(ks[3], n_heads * head_dim, d_model, bias=False, dtype=dtype),
    }


def _project_qkv(p: dict, x: jax.Array, n_heads: int, n_kv_heads: int,
                 head_dim: int, compute_dtype):
    b, s, _ = x.shape
    q = L.linear(p["wq"], x, compute_dtype).reshape(b, s, n_heads, head_dim)
    k = L.linear(p["wk"], x, compute_dtype).reshape(b, s, n_kv_heads, head_dim)
    v = L.linear(p["wv"], x, compute_dtype).reshape(b, s, n_kv_heads, head_dim)
    return q, k, v


def _apply_pos(q, k, positions, pos_embed: str, rope_theta: float,
               mrope_sections):
    if pos_embed == "rope":
        q = L.rope_apply(q, positions, rope_theta)
        k = L.rope_apply(k, positions, rope_theta)
    elif pos_embed == "mrope":
        q = L.mrope_apply(q, positions, mrope_sections, rope_theta)
        k = L.mrope_apply(k, positions, mrope_sections, rope_theta)
    # "none"/"sinusoidal": absolute embeddings are added at the stem.
    return q, k


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             *, q_chunk: int = 1024) -> jax.Array:
    """Causal GQA attention, scanned over query chunks.

    Memory is O(B * H * q_chunk * S) instead of O(B * H * S^2); FLOPs are
    unchanged. q: [B, S, H, D]; k, v: [B, S, Hkv, D]. Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q_chunk = min(q_chunk, s)
    assert s % q_chunk == 0, (s, q_chunk)
    n = s // q_chunk
    scale = 1.0 / (d ** 0.5)

    # bf16 operands + f32 accumulation (MXU-native): no f32 copies of K/V
    # are materialized (§Perf: halves the attention read traffic vs casting).
    f32 = jnp.float32
    qg = jnp.moveaxis(q.reshape(b, n, q_chunk, hkv, g, d), 1, 0)     # [N,B,C,Hkv,G,D]
    kpos = jnp.arange(s)

    def body(_, xs):
        qc, idx = xs                                   # [B,C,Hkv,G,D], scalar
        qpos = idx * q_chunk + jnp.arange(q_chunk)
        sc = jnp.einsum("bchgd,bshd->bchgs", qc, k,
                        preferred_element_type=f32) * scale
        mask = (qpos[:, None] >= kpos[None, :])[None, :, None, None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        oc = jnp.einsum("bchgs,bshd->bchgd", pr, v,
                        preferred_element_type=f32)
        return None, oc.astype(q.dtype)

    _, out = jax.lax.scan(body, None, (qg, jnp.arange(n)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hkv, g, d)
    return out.reshape(b, s, h, d)


def attention_train(p: dict, x: jax.Array, positions: jax.Array, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    pos_embed: str = "rope", rope_theta: float = 10000.0,
                    mrope_sections=(16, 24, 24), q_chunk: int = 1024,
                    compute_dtype=None) -> jax.Array:
    """Full-sequence causal attention (training / prefill compute)."""
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, compute_dtype)
    q, k = _apply_pos(q, k, positions, pos_embed, rope_theta, mrope_sections)
    out = chunked_causal_attention(q, k, v, q_chunk=q_chunk)
    b, s = x.shape[:2]
    return L.linear(p["wo"], out.reshape(b, s, n_heads * head_dim), compute_dtype)


def attention_prefill(p: dict, x: jax.Array, positions: jax.Array,
                      cache_k: jax.Array, cache_v: jax.Array, *,
                      n_heads: int, n_kv_heads: int, head_dim: int,
                      pos_embed: str = "rope", rope_theta: float = 10000.0,
                      mrope_sections=(16, 24, 24), q_chunk: int = 1024,
                      compute_dtype=None):
    """Prefill: attend causally over the prompt AND populate the KV cache.

    cache_k/v: [B, S_max, Hkv, D] with S_max >= S. Returns (out, k', v').
    """
    b, s = x.shape[:2]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, compute_dtype)
    q, k = _apply_pos(q, k, positions, pos_embed, rope_theta, mrope_sections)
    out = chunked_causal_attention(q, k, v, q_chunk=q_chunk)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, 0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, 0, 0, 0))
    out = L.linear(p["wo"], out.reshape(b, s, n_heads * head_dim), compute_dtype)
    return out, cache_k, cache_v


def attention_prefill_chunk(p: dict, x: jax.Array, offset: jax.Array,
                            chunk_len: jax.Array, cache_k: jax.Array,
                            cache_v: jax.Array, *,
                            n_heads: int, n_kv_heads: int, head_dim: int,
                            pos_embed: str = "rope",
                            rope_theta: float = 10000.0,
                            mrope_sections=(16, 24, 24),
                            kernel_mode: Literal["reference", "multiport"] = "reference",
                            seq_tile: int = 128,
                            dynamic_grid: bool = False,
                            interpret: bool | None = None,
                            mesh=None, mesh_axis: str = "kv",
                            port_mix: str = "wr",
                            compute_dtype=None):
    """One fixed-size prompt chunk per sequence, mid-prefill.

    The chunked-prefill analogue of the multi-port decode step: the cache is
    serviced as a 2-port memory — the W port scatters the chunk's K,V at
    positions [offset, offset+chunk_len) and the R port attends causally over
    everything cached so far INCLUDING the just-written chunk (same-cycle
    W->R visibility, exactly the FSM's priority order). ``kernel_mode``
    selects the fused length-bounded Pallas traversal (``"multiport"``, tiles
    [0, ceil((offset+chunk_len)/seq_tile)) only) or the two-pass jnp oracle
    (``"reference"``, an O(S_max) dense read per chunk).

    x: [B, C, d] chunk activations (rows >= chunk_len are padding);
    offset/chunk_len: [B] int32 per-sequence cache offset / valid-row count;
    cache_k/v: [B, S_max, Hkv, D]. Returns (out [B, C, d], k', v').
    Padded rows produce garbage outputs — callers gather row chunk_len-1.
    """
    b, c = x.shape[:2]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, compute_dtype)
    rel = jnp.arange(c)
    positions = offset[:, None] + rel[None, :]                    # [B, C]
    if pos_embed == "mrope":
        pos3 = jnp.broadcast_to(positions[..., None], (b, c, 3))
        q = L.mrope_apply(q, pos3, mrope_sections, rope_theta)
        k = L.mrope_apply(k, pos3, mrope_sections, rope_theta)
    elif pos_embed == "rope":
        q = L.rope_apply(q, positions, rope_theta)
        k = L.rope_apply(k, positions, rope_theta)

    new_k = k.astype(cache_k.dtype)
    new_v = v.astype(cache_v.dtype)
    if kernel_mode == "multiport":
        from repro.kernels import ops
        out, cache_k, cache_v = ops.fused_prefill_chunk_attention(
            q, cache_k, cache_v, new_k, new_v, offset, chunk_len,
            seq_tile=seq_tile, dynamic_grid=dynamic_grid, interpret=interpret,
            mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix)
    else:
        from repro.kernels import ref
        out, cache_k, cache_v = ref.prefill_chunk_attention_ref(
            q, cache_k, cache_v, new_k, new_v, offset, chunk_len)
    out = out.reshape(b, c, n_heads * head_dim)
    return L.linear(p["wo"], out, compute_dtype), cache_k, cache_v


def attention_decode(p: dict, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, cache_len: jax.Array, *,
                     n_heads: int, n_kv_heads: int, head_dim: int,
                     pos_embed: str = "rope", rope_theta: float = 10000.0,
                     mrope_sections=(16, 24, 24),
                     kernel_mode: Literal["reference", "multiport"] = "reference",
                     seq_tile: int = 128, length_mask: bool = True,
                     dynamic_grid: bool = False, num_kv_splits: int = 1,
                     interpret: bool | None = None,
                     mesh=None, mesh_axis: str = "kv",
                     port_mix: str = "wr",
                     compute_dtype=None):
    """One decode step. x: [B, 1, d]; cache_k/v: [B, S_max, Hkv, D];
    cache_len: [B] current lengths. Returns (out [B,1,d], k', v').

    The multiport path traverses ``seq_tile``-sized cache tiles and, under
    ``length_mask``, skips tiles past each sequence's live length — callers
    additionally bound S_max itself by staging a bucketed live prefix.
    ``num_kv_splits > 1`` breaks each sequence's traversal into that many
    grid-parallel partial-attention chains (split-KV flash-decode; 1 is
    the serial oracle). ``mesh`` runs the fused traversal under
    ``shard_map`` over the batch axis (data-parallel KV: each device's
    kernel sees only its own sequences' SMEM scalars and live-tile bound).
    """
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, compute_dtype)
    if pos_embed == "mrope":
        # text-only decode advances all three streams together
        pos3 = jnp.broadcast_to(cache_len[:, None, None], (b, 1, 3))
        q = L.mrope_apply(q, pos3, mrope_sections, rope_theta)
        k = L.mrope_apply(k, pos3, mrope_sections, rope_theta)
    elif pos_embed == "rope":
        pos = cache_len[:, None]
        q = L.rope_apply(q, pos, rope_theta)
        k = L.rope_apply(k, pos, rope_theta)

    q1 = q[:, 0]                                       # [B, H, D]
    new_k = k[:, 0].astype(cache_k.dtype)
    new_v = v[:, 0].astype(cache_v.dtype)

    if kernel_mode == "multiport":
        from repro.kernels import ops
        out, cache_k, cache_v = ops.fused_decode_attention(
            q1, cache_k, cache_v, new_k, new_v, cache_len,
            seq_tile=seq_tile, length_mask=length_mask,
            dynamic_grid=dynamic_grid, num_kv_splits=num_kv_splits,
            interpret=interpret,
            mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix)
    else:
        from repro.kernels import ref
        out, cache_k, cache_v = ref.decode_attention_ref(
            q1, cache_k, cache_v, new_k, new_v, cache_len)
    out = L.linear(p["wo"], out.reshape(b, 1, n_heads * head_dim)[..., :],
                   compute_dtype)
    return out, cache_k, cache_v
