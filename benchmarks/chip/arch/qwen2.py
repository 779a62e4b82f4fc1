"""Qwen2 (``model_type`` ``qwen2``): the program's registry entry it
needs, its per-token matmul parameters, and the plain reference — a Qwen2
decoder in ``jax.numpy``, float32 at HIGHEST matmul precision, layer by
layer — with its lower-precision control.

``program(conf)`` gives the registry ``ArchConfig`` attributes the
configuration file fixes; the file must state ``hidden_size ==
num_attention_heads * head_dim``, as every Qwen2 does. ``matmul_params(conf)``
counts the non-embedding matmul parameters one token passes through: the
attention projections and the SwiGLU MLP of every layer.

The reference imports nothing of the program. It reads the benchmark's
own weights (``weights.py``) by the names of the program's parameter tree:
``embed.w``, per layer ``ln1.scale``, ``attn.{wq,wk,wv}.{w,b}``,
``attn.wo.w``, ``ln2.scale``, ``ffn.{w_gate,w_up,w_down}.w``, then
``final_norm.scale`` and ``lm_head.w``, or, where the configuration ties
the output head to the embedding, ``embed.w`` transposed. The layer
follows the published Qwen2 description: pre-RMSNorm, grouped-query
attention with QKV bias and rotate-half RoPE, a SwiGLU MLP; widths,
``rope_theta`` and ``rms_norm_eps`` come from the configuration file.

``check`` runs it over each sampled request's prompt and served tokens
(teacher forcing) and returns, for every served token, the gap by which
the token's reference logit lies below the reference's best logit at that
position. With ``control=True`` it also runs the control — the same model
computed in int8 (weights per output channel, activations per token,
symmetric) — and returns the gap of the token the control puts first, at
the same positions. A correct greedy program reads near-tie gaps only;
the control shows what the next precision down would read. A decoder
that differs only in its layer passes its own jitted ``layer`` (same
arguments as ``_layer``) to ``hidden`` and ``check``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ROWS = 8            # sequences per reference batch
HEAD_ROWS = 256     # positions per lm_head block


def program(conf: dict) -> dict:
    """The registry ``ArchConfig`` attributes the file fixes, with their
    values. Raises ValueError where the file is no Qwen2."""
    hd = conf["num_attention_heads"] * conf["head_dim"]
    if conf["hidden_size"] != hd:
        raise ValueError(f"hidden_size {conf['hidden_size']} is not "
                         f"num_attention_heads * head_dim = {hd}")
    return {"n_layers": conf["num_hidden_layers"],
            "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim_": conf["head_dim"],
            "d_ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"],
            "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["rms_norm_eps"]), "qkv_bias": True}


def matmul_params(conf: dict) -> int:
    """Non-embedding matmul parameters one token passes through: Q, K, V
    and O, and the three SwiGLU matrices, in every layer."""
    d = conf["hidden_size"]
    hd = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    per_layer = d * hd + 2 * d * kv + hd * d \
        + 3 * d * conf["intermediate_size"]
    return per_layer * conf["num_hidden_layers"]


def _int8(x, axis):
    """Symmetric int8 round trip along ``axis`` (one scale per slice)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8: bool):
    w = w.astype(F32)
    if int8:
        x, w = _int8(x, -1), _int8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos[..., None].astype(F32) * inv            # [R, S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("w", "int8"))
def _layer(x, layers, i, *, w, int8: bool):
    p = jax.tree.map(lambda a: a[i], layers)
    r, s, _ = x.shape
    a = p["attn"]
    h = _norm(x, p["ln1"]["scale"], w.norm_eps)

    def proj(name, heads):
        y = _mm(h, a[name]["w"], int8) + a[name]["b"].astype(F32)
        return y.reshape(r, s, heads, w.head_dim)
    pos = jnp.broadcast_to(jnp.arange(s), (r, s))
    q = _rope(proj("wq", w.heads), pos, w.rope_theta)
    k = _rope(proj("wk", w.kv_heads), pos, w.rope_theta)
    v = proj("wv", w.kv_heads)
    g = w.heads // w.kv_heads
    q = q.reshape(r, s, w.kv_heads, g, w.head_dim)
    sc = jnp.einsum("rshgd,rthd->rhgst", q, k, precision=HI)
    sc = sc / np.sqrt(w.head_dim)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("rhgst,rthd->rshgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(r, s, w.heads * w.head_dim)
    x = x + _mm(o, a["wo"]["w"], int8)
    f = p["ffn"]
    h = _norm(x, p["ln2"]["scale"], w.norm_eps)
    gate = _mm(h, f["w_gate"]["w"], int8)
    up = _mm(h, f["w_up"]["w"], int8)
    return x + _mm(jax.nn.silu(gate) * up, f["w_down"]["w"], int8)


def hidden(params, w, ids: np.ndarray, *, int8: bool, layer=_layer
           ) -> jax.Array:
    """Final-layer hidden states ``[R, S, hidden]`` for token ids
    ``[R, S]`` (rows padded at the end; attention is causal)."""
    x = jnp.take(params["embed"]["w"], jnp.asarray(ids), axis=0).astype(F32)
    for i in range(w.layers):
        x = layer(x, params["layers"], i, w=w, int8=int8)
    return x


@functools.partial(jax.jit, static_argnames=("w", "control"))
def _gaps(h, hc, norm, lm_head, served, *, w, control: bool):
    """Per position: the reference's best logit less that of the served
    token and, with ``control``, less that of the control's first choice.
    ``h``/``hc``: reference / control final hidden states ``[N, hidden]``."""
    z = _mm(_norm(h, norm, w.norm_eps), lm_head, False)
    best = z.max(-1)
    gap = best - jnp.take_along_axis(z, served[:, None], -1)[:, 0]
    if not control:
        return gap, gap
    pick = jnp.argmax(_mm(_norm(hc, norm, w.norm_eps), lm_head, True), -1)
    return gap, best - jnp.take_along_axis(z, pick[:, None], -1)[:, 0]


def check(params, w, seqs: list, max_len: int, *, control: bool = False,
          layer=_layer) -> dict:
    """``seqs``: ``[(prompt, served)]`` token lists. Returns ``{"gaps":
    [...]}`` (one per served token, in order) and, with ``control``,
    ``"control_gaps"``. Every program it runs has one shape: batches of
    ``ROWS`` sequences padded to ``max_len``, heads over ``HEAD_ROWS``
    positions at a time."""
    gaps, cgaps = [], []
    norm = params["final_norm"]["scale"]
    lm = params["embed"]["w"].T if w.tied else params["lm_head"]["w"]
    with jax.default_matmul_precision("highest"):
        for b in range(0, len(seqs), ROWS):
            ids = np.zeros((ROWS, max_len), np.int32)
            served = np.zeros((ROWS, max_len), np.int32)
            want = np.zeros((ROWS, max_len), bool)
            for j, (prompt, out) in enumerate(seqs[b:b + ROWS]):
                full = list(prompt) + list(out[:-1])
                ids[j, :len(full)] = full
                n = len(prompt)
                served[j, n - 1:n - 1 + len(out)] = out
                want[j, n - 1:n - 1 + len(out)] = True
            d = w.hidden
            h = hidden(params, w, ids, int8=False,
                       layer=layer).reshape(-1, d)
            hc = (hidden(params, w, ids, int8=True,
                         layer=layer).reshape(-1, d)
                  if control else h)
            srv = jnp.asarray(served.reshape(-1))
            g, c = [], []
            for a in range(0, h.shape[0], HEAD_ROWS):
                sl = slice(a, a + HEAD_ROWS)
                x, y = _gaps(h[sl], hc[sl], norm, lm, srv[sl], w=w,
                             control=control)
                g.append(np.asarray(x))
                c.append(np.asarray(y))
            keep = want.reshape(-1)
            gaps += np.concatenate(g)[keep].tolist()
            cgaps += np.concatenate(c)[keep].tolist()
    out = {"gaps": gaps}
    if control:
        out["control_gaps"] = cgaps
    return out
