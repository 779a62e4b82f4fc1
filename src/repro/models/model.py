"""Model assembly: init / forward / loss / prefill / decode for all families.

Layer stacks are scanned (``lax.scan`` over params stacked on a leading
[n_layers] axis) so the HLO is O(1) in depth — essential for compiling the
126-layer llama3-405b dry-run. Remat wraps the scan body (``cfg.remat``).

Batch dict convention:
  train/prefill: {"inputs": ids[B,S] | embeds[B,S,d], "labels": ids[B,S],
                  "positions": optional ([B,S] rope / [B,S,3] mrope)}
  decode:        {"inputs": ids[B,1] | embeds[B,1,d]}

Decode state (per family) is a dict pytree with a shared "len": [B] field.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.api import hint
from repro.models import blocks as B
from repro.models import layers as L

PyTree = Any


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _hybrid_counts(cfg: ArchConfig) -> tuple[int, int, int]:
    g = cfg.n_layers // cfg.hybrid.group_size
    m = cfg.hybrid.group_size
    tail = cfg.n_layers - g * m
    return g, m, tail


def init_params(key, cfg: ArchConfig) -> PyTree:
    ks = jax.random.split(key, 6)
    p: dict = {}
    if cfg.input_mode == "tokens":
        p["embed"] = L.embedding_init(ks[0], cfg.vocab, cfg.d_model, cfg.pdtype)
    p["final_norm"] = L.rmsnorm_init(cfg.d_model, cfg.pdtype)
    p["lm_head"] = L.linear_init(ks[1], cfg.d_model, cfg.vocab, dtype=cfg.pdtype)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        p["layers"] = _stacked_init(
            lambda k: B.transformer_block_init(k, cfg), ks[2], cfg.n_layers)
    elif cfg.family == "ssm":
        p["layers"] = _stacked_init(
            lambda k: B.rwkv_block_init(k, cfg), ks[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        g, m, tail = _hybrid_counts(cfg)
        p["shared_attn"] = B.transformer_block_init(
            ks[3], cfg, d_ff=cfg.hybrid.attn_d_ff)
        p["groups"] = jax.vmap(
            lambda k: _stacked_init(lambda kk: B.mamba_block_init(kk, cfg), k, m)
        )(jax.random.split(ks[2], g))
        if tail:
            p["tail"] = _stacked_init(
                lambda k: B.mamba_block_init(k, cfg), ks[4], tail)
    else:
        raise ValueError(cfg.family)
    return p


# --------------------------------------------------------------------------
# stem & head
# --------------------------------------------------------------------------

def _stem(params: PyTree, cfg: ArchConfig, inputs: jax.Array,
          offset: jax.Array | int = 0) -> jax.Array:
    if cfg.input_mode == "tokens":
        x = L.embedding_lookup(params["embed"], inputs, cfg.cdtype)
    else:
        x = inputs.astype(cfg.cdtype)
    if cfg.pos_embed == "sinusoidal":
        s = x.shape[1]
        if isinstance(offset, int):
            pe = L.sinusoidal_positions(s, cfg.d_model, offset)[None]
        else:  # per-sample offsets (decode)
            pe = jax.vmap(lambda o: L.sinusoidal_positions(s, cfg.d_model, o))(offset)
        x = x + pe.astype(x.dtype)
    return hint(x, "hidden")


def _default_positions(cfg: ArchConfig, batch: dict, b: int, s: int) -> jax.Array:
    pos = batch.get("positions")
    if pos is not None:
        return pos
    base = jnp.arange(s)[None]
    if cfg.pos_embed == "mrope":
        return jnp.broadcast_to(base[..., None], (b, s, 3))
    return jnp.broadcast_to(base, (b, s))


def _head(params: PyTree, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return hint(L.linear(params["lm_head"], x, cfg.cdtype), "logits")


# --------------------------------------------------------------------------
# forward (training compute)
# --------------------------------------------------------------------------

def forward(params: PyTree, cfg: ArchConfig, batch: dict
            ) -> tuple[jax.Array, jax.Array]:
    """Returns (logits [B,S,V], moe_aux_loss scalar)."""
    inputs = batch["inputs"]
    bsz = inputs.shape[0]
    seq = inputs.shape[1]
    x = _stem(params, cfg, inputs)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        positions = _default_positions(cfg, batch, bsz, seq)

        def body(carry, pl):
            h, aux = carry
            h, a = B.transformer_block_apply(pl, h, positions, cfg)
            return (h, aux + a), None
        if cfg.remat == "block":
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])

    elif cfg.family == "ssm":
        def body(h, pl):
            return B.rwkv_block_apply(pl, h, cfg), None
        if cfg.remat == "block":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = aux0

    elif cfg.family == "hybrid":
        positions = _default_positions(cfg, batch, bsz, seq)
        shared = params["shared_attn"]

        def group_body(h, pg):
            h, _ = B.transformer_block_apply(shared, h, positions, cfg)

            def inner(hh, pl):
                return B.mamba_block_apply(pl, hh, cfg), None
            h, _ = jax.lax.scan(inner, h, pg)
            return h, None
        if cfg.remat == "block":
            group_body = jax.checkpoint(group_body)
        x, _ = jax.lax.scan(group_body, x, params["groups"])
        if "tail" in params:
            def tail_body(h, pl):
                return B.mamba_block_apply(pl, h, cfg), None
            if cfg.remat == "block":
                tail_body = jax.checkpoint(tail_body)
            x, _ = jax.lax.scan(tail_body, x, params["tail"])
        aux = aux0
    else:
        raise ValueError(cfg.family)

    return _head(params, cfg, x), aux


def loss_fn(params: PyTree, cfg: ArchConfig, batch: dict
            ) -> tuple[jax.Array, dict]:
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    mask = (labels >= 0)
    labels = jnp.maximum(labels, 0)
    lf = logits.astype(jnp.float32)
    # Vocab-sharding-friendly CE: every vocab-axis op is a reduction (the
    # gold logit is a one-hot contraction, not a gather), so a tensor-parallel
    # vocab stays sharded through fwd+bwd — no [B,S,V] all-gather.
    m = jax.lax.stop_gradient(lf.max(axis=-1))
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    onehot = (labels[..., None] ==
              jax.lax.broadcasted_iota(jnp.int32, lf.shape[-1:], 0))
    gold = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    nll = (lse - gold) * mask
    count = jnp.maximum(mask.sum(), 1)
    ce = nll.sum() / count
    zl = cfg.z_loss * ((lse * mask) ** 2).sum() / count
    loss = ce + zl + aux
    acc = ((lf.argmax(-1) == labels) * mask).sum() / count
    return loss, {"loss": loss, "ce": ce, "z_loss": zl, "moe_aux": aux,
                  "accuracy": acc, "tokens": count}


# --------------------------------------------------------------------------
# decode state
# --------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch_size: int, max_len: int) -> PyTree:
    cdt = cfg.cdtype
    hd = cfg.head_dim_
    state: dict = {"len": jnp.zeros((batch_size,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        kv = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, hd)
        state["cache_k"] = jnp.zeros(kv, cdt)
        state["cache_v"] = jnp.zeros(kv, cdt)
    elif cfg.family == "ssm":
        h = cfg.d_model // cfg.rwkv.head_dim
        k = cfg.rwkv.head_dim
        lshape = (cfg.n_layers, batch_size)
        state["tm_shift"] = jnp.zeros(lshape + (cfg.d_model,), cdt)
        state["tm_state"] = jnp.zeros(lshape + (h, k, k), jnp.float32)
        state["cm_shift"] = jnp.zeros(lshape + (cfg.d_model,), cdt)
    elif cfg.family == "hybrid":
        g, m, tail = _hybrid_counts(cfg)
        ssm = cfg.ssm
        d_in = ssm.expand * cfg.d_model
        nheads = d_in // ssm.head_dim
        kv = (g, batch_size, max_len, cfg.n_kv_heads, hd)
        state["attn_k"] = jnp.zeros(kv, cdt)
        state["attn_v"] = jnp.zeros(kv, cdt)

        def conv_states(*lead):
            ck = ssm.conv_kernel - 1
            return {"x": jnp.zeros(lead + (batch_size, ck, d_in), cdt),
                    "B": jnp.zeros(lead + (batch_size, ck, ssm.state_dim), cdt),
                    "C": jnp.zeros(lead + (batch_size, ck, ssm.state_dim), cdt)}
        state["conv"] = conv_states(g, m)
        state["ssm"] = jnp.zeros((g, m, batch_size, nheads, ssm.state_dim,
                                  ssm.head_dim), jnp.float32)
        if tail:
            state["tail_conv"] = conv_states(tail)
            state["tail_ssm"] = jnp.zeros((tail, batch_size, nheads, ssm.state_dim,
                                           ssm.head_dim), jnp.float32)
    return state


# --------------------------------------------------------------------------
# decode step (one new token; KV caches serviced as multi-port memory)
# --------------------------------------------------------------------------

def decode_step(params: PyTree, cfg: ArchConfig, state: PyTree, batch: dict,
                *, kernel_mode: str = "reference", seq_tile: int = 128,
                length_mask: bool = True, dynamic_grid: bool = False,
                num_kv_splits: int = 1,
                interpret: bool | None = None, mesh=None,
                mesh_axis: str = "kv",
                port_mix: str = "wr") -> tuple[PyTree, jax.Array]:
    """Returns (state', logits [B, V]).

    ``seq_tile``/``length_mask`` bound the multiport kernel's traversal to
    live cache tiles; callers bound the allocated length itself by passing a
    state whose caches hold a bucketed live prefix (the engine does both).
    ``num_kv_splits > 1`` runs each attention layer's traversal as split-KV
    flash-decode (grid-parallel partials + LSE combine; 1 = serial oracle).
    ``mesh`` (data-parallel KV) runs the fused traversal under ``shard_map``
    over the batch axis — per-device SMEM scalars and live-tile bounds.
    """
    inputs = batch["inputs"]
    x = _stem(params, cfg, inputs, offset=state["len"])

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def body(h, xs):
            pl, ck, cv = xs
            h, ck, cv = B.transformer_block_decode(
                pl, h, ck, cv, state["len"], cfg, kernel_mode=kernel_mode,
                seq_tile=seq_tile, length_mask=length_mask,
                dynamic_grid=dynamic_grid, num_kv_splits=num_kv_splits,
                interpret=interpret,
                mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix)
            return h, (ck, cv)
        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], state["cache_k"], state["cache_v"]))
        state = dict(state, cache_k=ck, cache_v=cv)

    elif cfg.family == "ssm":
        def body(h, xs):
            pl, tms, tmst, cms = xs
            h, (tms, tmst, cms) = B.rwkv_block_decode(pl, h, cfg, (tms, tmst, cms))
            return h, (tms, tmst, cms)
        x, (tms, tmst, cms) = jax.lax.scan(
            body, x, (params["layers"], state["tm_shift"], state["tm_state"],
                      state["cm_shift"]))
        state = dict(state, tm_shift=tms, tm_state=tmst, cm_shift=cms)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group_body(h, xs):
            pg, ck, cv, conv, ssm_s = xs
            h, ck, cv = B.transformer_block_decode(
                shared, h, ck, cv, state["len"], cfg, kernel_mode=kernel_mode,
                seq_tile=seq_tile, length_mask=length_mask,
                dynamic_grid=dynamic_grid, num_kv_splits=num_kv_splits,
                interpret=interpret,
                mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix)

            def inner(hh, ys):
                pl, cs, ss = ys
                hh, cs, ss = B.mamba_block_decode(pl, hh, cfg, cs, ss)
                return hh, (cs, ss)
            h, (conv, ssm_s) = jax.lax.scan(inner, h, (pg, conv, ssm_s))
            return h, (ck, cv, conv, ssm_s)

        x, (ck, cv, conv, ssm_s) = jax.lax.scan(
            group_body, x, (params["groups"], state["attn_k"], state["attn_v"],
                            state["conv"], state["ssm"]))
        state = dict(state, attn_k=ck, attn_v=cv, conv=conv, ssm=ssm_s)
        if "tail" in params:
            def tail_body(h, ys):
                pl, cs, ss = ys
                h, cs, ss = B.mamba_block_decode(pl, h, cfg, cs, ss)
                return h, (cs, ss)
            x, (tcs, tss) = jax.lax.scan(
                tail_body, x, (params["tail"], state["tail_conv"],
                               state["tail_ssm"]))
            state = dict(state, tail_conv=tcs, tail_ssm=tss)
    else:
        raise ValueError(cfg.family)

    logits = _head(params, cfg, x)[:, 0]
    state = dict(state, len=state["len"] + 1)
    return state, logits


# --------------------------------------------------------------------------
# prefill (populate caches from a prompt)
# --------------------------------------------------------------------------

def prefill(params: PyTree, cfg: ArchConfig, state: PyTree, batch: dict
            ) -> tuple[PyTree, jax.Array]:
    """Process a prompt of length S, filling caches. Returns (state', logits
    of the last position [B, V])."""
    inputs = batch["inputs"]
    bsz, seq = inputs.shape[0], inputs.shape[1]
    x = _stem(params, cfg, inputs)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        positions = _default_positions(cfg, batch, bsz, seq)

        def body(h, xs):
            pl, ck, cv = xs
            h, ck, cv = B.transformer_block_prefill(pl, h, positions, ck, cv, cfg)
            return h, (ck, cv)
        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], state["cache_k"], state["cache_v"]))
        state = dict(state, cache_k=ck, cache_v=cv)

    elif cfg.family == "ssm":
        def body(h, xs):
            pl, tms, tmst, cms = xs
            h, st = B.rwkv_block_apply(pl, h, cfg, states=(None, tmst, None),
                                       return_state=True)
            return h, st
        x, (tms, tmst, cms) = jax.lax.scan(
            body, x, (params["layers"], state["tm_shift"], state["tm_state"],
                      state["cm_shift"]))
        state = dict(state, tm_shift=tms, tm_state=tmst, cm_shift=cms)

    elif cfg.family == "hybrid":
        positions = _default_positions(cfg, batch, bsz, seq)
        shared = params["shared_attn"]

        def group_body(h, xs):
            pg, ck, cv, conv, ssm_s = xs
            h, ck, cv = B.transformer_block_prefill(shared, h, positions, ck, cv, cfg)

            def inner(hh, ys):
                pl, cs, ss = ys
                hh, cs, ss = B.mamba_block_apply(pl, hh, cfg, conv_state=None,
                                                 ssm_state=ss, return_state=True)
                return hh, (cs, ss)
            h, (conv, ssm_s) = jax.lax.scan(inner, h, (pg, conv, ssm_s))
            return h, (ck, cv, conv, ssm_s)

        x, (ck, cv, conv, ssm_s) = jax.lax.scan(
            group_body, x, (params["groups"], state["attn_k"], state["attn_v"],
                            state["conv"], state["ssm"]))
        state = dict(state, attn_k=ck, attn_v=cv, conv=conv, ssm=ssm_s)
        if "tail" in params:
            def tail_body(h, ys):
                pl, cs, ss = ys
                h, cs, ss = B.mamba_block_apply(pl, h, cfg, conv_state=None,
                                                ssm_state=ss, return_state=True)
                return h, (cs, ss)
            x, (tcs, tss) = jax.lax.scan(
                tail_body, x, (params["tail"], state["tail_conv"],
                               state["tail_ssm"]))
            state = dict(state, tail_conv=tcs, tail_ssm=tss)

    logits = _head(params, cfg, x[:, -1:])[:, 0]
    state = dict(state, len=state["len"] + seq)
    return state, logits


# --------------------------------------------------------------------------
# chunked prefill (populate caches one fixed-size chunk per macro-cycle)
# --------------------------------------------------------------------------

def prefill_chunk(params: PyTree, cfg: ArchConfig, state: PyTree, batch: dict,
                  *, kernel_mode: str = "reference", seq_tile: int = 128,
                  dynamic_grid: bool = False, interpret: bool | None = None,
                  mesh=None, mesh_axis: str = "kv", port_mix: str = "wr"
                  ) -> tuple[PyTree, jax.Array]:
    """Process ONE fixed-size prompt chunk for a batch of sequences.

    The continuous-batching prefill step: each sequence contributes its next
    ``C`` prompt tokens (rows past ``chunk_len`` are padding), chunks from
    different sequences are stacked into one padded batch, and every chunk's
    K,V is written into the cache at [len, len+chunk_len) while attention
    reads back over everything cached so far — the cache serviced as a
    2-port (1W+1R) memory, same as decode. Under
    ``kernel_mode="multiport"`` both ports run through the fused Pallas
    traversal bounded to live ``seq_tile``-tiles; ``"reference"`` keeps the
    two-pass jnp oracle and its O(S_max) dense read.

    batch: {"inputs": ids [B, C], "chunk_len": [B] valid rows per sequence}.
    Returns (state', logits [B, V]) where the logits row for each sequence is
    taken at its LAST VALID chunk position — when the chunk completes a
    prompt these are the prefill logits that seed the first generated token.
    """
    if cfg.family not in ("dense", "moe", "vlm", "audio"):
        raise NotImplementedError("chunked prefill serves KV-cache families")
    inputs = batch["inputs"]
    c = inputs.shape[1]
    chunk_len = jnp.asarray(batch["chunk_len"], jnp.int32)
    offset = state["len"]
    x = _stem(params, cfg, inputs, offset=offset)

    def body(h, xs):
        pl, ck, cv = xs
        h, ck, cv = B.transformer_block_prefill_chunk(
            pl, h, offset, chunk_len, ck, cv, cfg, kernel_mode=kernel_mode,
            seq_tile=seq_tile, dynamic_grid=dynamic_grid, interpret=interpret,
            mesh=mesh, mesh_axis=mesh_axis, port_mix=port_mix)
        return h, (ck, cv)
    x, (ck, cv) = jax.lax.scan(
        body, x, (params["layers"], state["cache_k"], state["cache_v"]))

    last = jnp.clip(chunk_len - 1, 0, c - 1)
    xl = jnp.take_along_axis(x, last[:, None, None], axis=1)      # [B, 1, d]
    logits = _head(params, cfg, xl)[:, 0]
    state = dict(state, cache_k=ck, cache_v=cv, len=offset + chunk_len)
    return state, logits
