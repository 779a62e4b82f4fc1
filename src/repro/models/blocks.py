"""Per-family layer blocks: init + train-apply + decode-step triples.

Block params are plain dicts; stacks are built by vmapping init over layer
keys so every leaf gains a leading [n_layers] axis for ``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import rwkv as R
from repro.models import ssm as S


# --------------------------------------------------------------------------
# transformer block (dense / moe / vlm / audio)
# --------------------------------------------------------------------------

def transformer_block_init(key, cfg: ArchConfig, *, d_ff: int | None = None) -> dict:
    ks = jax.random.split(key, 2)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "attn": A.attention_init(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim_, qkv_bias=cfg.qkv_bias,
                                 dtype=cfg.pdtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
    }
    if cfg.moe is not None and d_ff is None:
        p["moe"] = M.moe_init(ks[1], cfg.d_model, cfg.moe, dtype=cfg.pdtype)
    else:
        p["ffn"] = M.swiglu_init(ks[1], cfg.d_model, d_ff or cfg.d_ff,
                                 dtype=cfg.pdtype)
    return p


def transformer_block_apply(p: dict, x: jax.Array, positions: jax.Array,
                            cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    """Training/prefill-compute body. Returns (x', moe_aux)."""
    h = A.attention_train(
        p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        pos_embed=cfg.pos_embed, rope_theta=cfg.rope_theta,
        mrope_sections=tuple(cfg.mrope_sections), q_chunk=cfg.q_chunk,
        compute_dtype=cfg.cdtype)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        h, aux = M.moe_apply(p["moe"], y, cfg.moe, compute_dtype=cfg.cdtype)
    else:
        h = M.swiglu_apply(p["ffn"], y, compute_dtype=cfg.cdtype)
    return x + h, aux


def transformer_block_prefill(p: dict, x, positions, cache_k, cache_v,
                              cfg: ArchConfig):
    h, ck, cv = A.attention_prefill(
        p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
        cache_k, cache_v,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        pos_embed=cfg.pos_embed, rope_theta=cfg.rope_theta,
        mrope_sections=tuple(cfg.mrope_sections), q_chunk=cfg.q_chunk,
        compute_dtype=cfg.cdtype)
    x = x + h
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        h, _ = M.moe_apply(p["moe"], y, cfg.moe, compute_dtype=cfg.cdtype)
    else:
        h = M.swiglu_apply(p["ffn"], y, compute_dtype=cfg.cdtype)
    return x + h, ck, cv


def transformer_block_prefill_chunk(p: dict, x, offset, chunk_len,
                                    cache_k, cache_v, cfg: ArchConfig,
                                    kernel_mode: str = "reference",
                                    seq_tile: int = 128,
                                    dynamic_grid: bool = False,
                                    interpret: bool | None = None,
                                    mesh=None, mesh_axis: str = "kv",
                                    port_mix: str = "wr"):
    h, ck, cv = A.attention_prefill_chunk(
        p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), offset, chunk_len,
        cache_k, cache_v,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        pos_embed=cfg.pos_embed, rope_theta=cfg.rope_theta,
        mrope_sections=tuple(cfg.mrope_sections), kernel_mode=kernel_mode,
        seq_tile=seq_tile, dynamic_grid=dynamic_grid, interpret=interpret,
        mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix,
        compute_dtype=cfg.cdtype)
    x = x + h
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        h, _ = M.moe_apply(p["moe"], y, cfg.moe, compute_dtype=cfg.cdtype)
    else:
        h = M.swiglu_apply(p["ffn"], y, compute_dtype=cfg.cdtype)
    return x + h, ck, cv


def transformer_block_decode(p: dict, x, cache_k, cache_v, cache_len,
                             cfg: ArchConfig, kernel_mode: str = "reference",
                             seq_tile: int = 128, length_mask: bool = True,
                             dynamic_grid: bool = False,
                             num_kv_splits: int = 1,
                             interpret: bool | None = None,
                             mesh=None, mesh_axis: str = "kv",
                             port_mix: str = "wr"):
    h, ck, cv = A.attention_decode(
        p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cache_k, cache_v,
        cache_len,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
        pos_embed=cfg.pos_embed, rope_theta=cfg.rope_theta,
        mrope_sections=tuple(cfg.mrope_sections), kernel_mode=kernel_mode,
        seq_tile=seq_tile, length_mask=length_mask,
        dynamic_grid=dynamic_grid, num_kv_splits=num_kv_splits,
        interpret=interpret,
        mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix,
        compute_dtype=cfg.cdtype)
    x = x + h
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        h, _ = M.moe_apply(p["moe"], y, cfg.moe, compute_dtype=cfg.cdtype)
    else:
        h = M.swiglu_apply(p["ffn"], y, compute_dtype=cfg.cdtype)
    return x + h, ck, cv


# --------------------------------------------------------------------------
# mamba2 block (hybrid)
# --------------------------------------------------------------------------

def mamba_block_init(key, cfg: ArchConfig) -> dict:
    return {
        "ln": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "mamba": S.mamba2_init(key, cfg.d_model, cfg.ssm, dtype=cfg.pdtype),
    }


def mamba_block_apply(p: dict, x: jax.Array, cfg: ArchConfig,
                      conv_state=None, ssm_state=None, return_state=False):
    y = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    if return_state:
        h, cs, ss = S.mamba2_apply(p["mamba"], y, cfg.d_model, cfg.ssm,
                                   la_chunk=cfg.la_chunk, compute_dtype=cfg.cdtype,
                                   conv_state=conv_state, ssm_state=ssm_state,
                                   return_state=True)
        return x + h, cs, ss
    h = S.mamba2_apply(p["mamba"], y, cfg.d_model, cfg.ssm,
                       la_chunk=cfg.la_chunk, compute_dtype=cfg.cdtype)
    return x + h


def mamba_block_decode(p: dict, x, cfg: ArchConfig, conv_state, ssm_state):
    y = L.rmsnorm(p["ln"], x, cfg.norm_eps)
    h, cs, ss = S.mamba2_decode_step(p["mamba"], y, cfg.d_model, cfg.ssm,
                                     conv_state=conv_state, ssm_state=ssm_state,
                                     compute_dtype=cfg.cdtype)
    return x + h, cs, ss


# --------------------------------------------------------------------------
# rwkv block
# --------------------------------------------------------------------------

def rwkv_block_init(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "tm": R.time_mix_init(ks[0], cfg.d_model, cfg.rwkv, dtype=cfg.pdtype),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
        "cm": R.channel_mix_init(ks[1], cfg.d_model, cfg.d_ff, dtype=cfg.pdtype),
    }


def rwkv_block_apply(p: dict, x: jax.Array, cfg: ArchConfig,
                     states=None, return_state=False):
    """states: (tm_shift, tm_state, cm_shift) or None."""
    tm_shift = tm_state = cm_shift = None
    if states is not None:
        tm_shift, tm_state, cm_shift = states
    y = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if return_state:
        h, new_tm_shift, new_tm_state = R.time_mix_apply(
            p["tm"], y, cfg.rwkv, la_chunk=cfg.la_chunk,
            compute_dtype=cfg.cdtype, shift_state=tm_shift,
            ssm_state=tm_state, return_state=True)
        x = x + h
        y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        h, new_cm_shift = R.channel_mix_apply(
            p["cm"], y, compute_dtype=cfg.cdtype, shift_state=cm_shift,
            return_state=True)
        return x + h, (new_tm_shift, new_tm_state, new_cm_shift)
    h = R.time_mix_apply(p["tm"], y, cfg.rwkv, la_chunk=cfg.la_chunk,
                         compute_dtype=cfg.cdtype)
    x = x + h
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + R.channel_mix_apply(p["cm"], y, compute_dtype=cfg.cdtype)


def rwkv_block_decode(p: dict, x, cfg: ArchConfig, states):
    tm_shift, tm_state, cm_shift = states
    y = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    h, new_tm_shift, new_tm_state = R.time_mix_step(
        p["tm"], y, cfg.rwkv, shift_state=tm_shift, ssm_state=tm_state,
        compute_dtype=cfg.cdtype)
    x = x + h
    y = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    h, new_cm_shift = R.channel_mix_step(p["cm"], y, shift_state=cm_shift,
                                         compute_dtype=cfg.cdtype)
    return x + h, (new_tm_shift, new_tm_state, new_cm_shift)
