"""The whole model's work for the tokens a macro-cycle processed, for
``mfu``: two FLOPs per matmul parameter one token passes through
(``w.matmul_params``, counted by the architecture's module) for every
token computed (prompt tokens after prefix hits, and decoded tokens), two
per ``lm_head`` parameter for every token whose logits are needed (each
output token), and the attention of ``decode_attn`` and
``prefill_chunk``. Embedding lookups and norms are not counted.
"""
from __future__ import annotations

from benchmarks.chip.work import decode_attn, prefill_chunk

MATCH = ()


def count(w, step) -> tuple[float, float]:
    tokens = sum(n for _, n in step.chunks) + len(step.decode_rows)
    flops = (2 * w.matmul_params * tokens
             + 2 * w.hidden * w.vocab * step.out_tokens
             + decode_attn.count(w, step)[0]
             + prefill_chunk.count(w, step)[0])
    return float(flops), 0.0
