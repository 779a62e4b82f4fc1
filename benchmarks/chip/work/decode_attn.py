"""Decode attention (``kernels/kv_multiport.py``, serial and split-KV
stages): per decoded token, one query over ``n`` cached keys and values,
the new key and value written.

Per layer and row: ``4 * heads * head_dim * n`` FLOPs (QK and PV); bytes
read ``2 * (n - 1) * kv_heads * head_dim`` (K and V) plus the query,
written ``2 * kv_heads * head_dim`` plus the output. Memory-bound.
"""
from __future__ import annotations

MATCH = ("fused_decode_attention",)


def count(w, step) -> tuple[float, float]:
    hd, kv = w.heads * w.head_dim, w.kv_heads * w.head_dim
    flops = sum(4 * hd * n for n in step.decode_rows)
    elems = sum(2 * kv * n + 2 * hd for n in step.decode_rows)
    return float(flops * w.layers), float(elems * w.dtype_bytes * w.layers)
