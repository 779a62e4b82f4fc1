"""Prefill-chunk attention (``kernels/kv_prefill_chunk.py``): ``n``
prompt tokens at offset ``o``, each attending causally over the ``o``
cached keys and the chunk's own earlier ones.

Per layer and chunk: ``4 * heads * head_dim * (n * o + n * (n + 1) / 2)``
FLOPs; bytes ``2 * (o + n) * kv_heads * head_dim`` (K and V read or
written once) plus ``2 * n * heads * head_dim`` (queries in, outputs
out).
"""
from __future__ import annotations

MATCH = ("fused_prefill_chunk_attention",)


def count(w, step) -> tuple[float, float]:
    hd, kv = w.heads * w.head_dim, w.kv_heads * w.head_dim
    flops = sum(4 * hd * (n * o + n * (n + 1) // 2) for o, n in step.chunks)
    elems = sum(2 * kv * (o + n) + 2 * hd * n for o, n in step.chunks)
    return float(flops * w.layers), float(elems * w.dtype_bytes * w.layers)
