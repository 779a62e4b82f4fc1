"""Llama (``model_type`` ``llama``), as a new architecture brings it:
pre-RMSNorm, grouped-query attention without bias and with rotate-half
RoPE, a SwiGLU MLP. Its layer is Qwen2's without the QKV bias, so it
reuses ``arch/qwen2.py``'s helpers and its ``check`` with its own layer.
"""
from __future__ import annotations

import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import discover

qwen2 = discover.load_module(pathlib.Path(__file__).with_name("qwen2.py"))
HI = qwen2.HI
_mm, _norm, _rope = qwen2._mm, qwen2._norm, qwen2._rope


def program(conf: dict) -> dict:
    return {"n_layers": conf["num_hidden_layers"],
            "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim_": conf["head_dim"],
            "d_ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"],
            "rope_theta": float(conf["rope_theta"]),
            "norm_eps": float(conf["rms_norm_eps"]), "qkv_bias": False}


def matmul_params(conf: dict) -> int:
    d = conf["hidden_size"]
    hd = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    per_layer = d * hd + 2 * d * kv + hd * d \
        + 3 * d * conf["intermediate_size"]
    return per_layer * conf["num_hidden_layers"]


@functools.partial(jax.jit, static_argnames=("w", "int8"))
def _layer(x, layers, i, *, w, int8: bool):
    p = jax.tree.map(lambda a: a[i], layers)
    r, s, _ = x.shape
    a = p["attn"]
    h = _norm(x, p["ln1"]["scale"], w.norm_eps)

    def proj(name, heads):
        return _mm(h, a[name]["w"], int8).reshape(r, s, heads, w.head_dim)
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


check = functools.partial(qwen2.check, layer=_layer)
