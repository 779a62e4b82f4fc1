"""Paged-pool traversal (``memory/paged_kv.py:cycle`` ->
``kernels/multiport_sram.py``): the words a macro-cycle writes (prompt
chunks, decode appends, copy-on-write copies), reads (each decode row's
live cache) and scrubs (pages freed at eviction), each moved once.

A word is one token's K and V over all layers, ``2 * layers * kv_heads *
head_dim`` elements at the configuration's dtype. Bytes only: the one-hot
matmul that moves words on today's MXU is how the kernel does the work,
not the work, so its FLOPs are not counted. Bandwidth-bound.
"""
from __future__ import annotations

MATCH = ("_pool_step",)


def count(w, step) -> tuple[float, float]:
    word = 2 * w.layers * w.kv_heads * w.head_dim * w.dtype_bytes
    return 0.0, float((step.words_written + step.words_read
                       + step.words_scrubbed) * word)
