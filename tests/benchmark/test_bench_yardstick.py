"""The chip benchmark's yardstick on the CPU: the traffic generator, the
percentile and rate arithmetic, and each kernel's work count."""
import collections
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import discover, stats  # noqa: E402
from benchmarks.chip.driver import StepWork  # noqa: E402
from benchmarks.chip.generators import open_loop  # noqa: E402
from benchmarks.chip.work import (decode_attn, model_step,  # noqa: E402
                                  pool_step, prefill_chunk)

RAG = {"generator": "open_loop", "arrivals": "poisson", "rate_per_s": 3.0,
       "warmup_burst": 4,
       "prompt_tokens": {"alpha": 1.2, "min": 288, "max": 448},
       "output_tokens": {"alpha": 1.2, "min": 4, "max": 32},
       "shared_headers": {"count": 8, "tokens": 256}, "check_tokens": 64}


def _gen(params, seed, seconds=20.0):
    return open_loop.generate(params, seed=seed, seconds=seconds, vocab=1000,
                              warmup_s=10.0)


def test_same_seed_same_requests():
    assert _gen(RAG, 2**33 + 5) == _gen(RAG, 2**33 + 5)
    assert _gen(RAG, 1) != _gen(RAG, 2)


def test_every_seed_offers_the_same_work():
    """Seeds permute one multiset of lengths and gaps per phase."""
    def shape(arr):
        return {ph: (sorted(len(a.prompt) for a in arr if a.phase == ph),
                     sorted(a.max_new for a in arr if a.phase == ph))
                for ph in ("burst", "warmup", "window")}
    a, b = _gen(RAG, 3), _gen(RAG, 4)
    assert shape(a) == shape(b)
    n = collections.Counter(x.phase for x in a)
    assert n == {"burst": 4, "warmup": 30, "window": 60}
    win = [x.due_s for x in a if x.phase == "window"]
    assert 0 <= min(win) and max(win) < 20
    assert [x.phase for x in a] == sorted(
        (x.phase for x in a), key=lambda p: p == "window")


def test_header_overlay_keeps_lengths_and_times():
    plain = dict(RAG, shared_headers=None)
    a, b = _gen(RAG, 9), _gen(plain, 9)
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new) for x in b]
    heads = {x.prompt[:256] for x in a}
    assert len(heads) == 8
    assert all(x.prompt[256:] == y.prompt[256:] for x, y in zip(a, b))


def test_backlog_is_due_at_once():
    p = dict(RAG, arrivals="backlog", backlog=12, warmup_burst=0)
    a = _gen(p, 1)
    assert len(a) == 12 and {x.due_s for x in a} == {0.0}


def test_bounded_pareto_quantiles_heavy_tail():
    q = open_loop.bounded_pareto_quantiles(1.2, 32, 256, 1000)
    assert q.min() == 32 and q.max() <= 256
    assert 60 < q.mean() < 80           # the bounded-Pareto mean is ~71
    assert (q < 64).mean() > 0.5


def test_a_stall_moves_the_tail():
    """Four requests stream a token every 0.1 s through a 10 s window; the
    engine stalls for 1 s every 2 s. The 95th-percentile gap and the rate
    must show the stalls, though the median gap does not move."""
    def stream(stall):
        t, out = 0.0, [0.0]
        while t < 10.0:
            t += 0.1 + (1.0 if stall and len(out) % 10 == 0 else 0.0)
            out.append(t)
        return out
    for stall, p95, per_req in ((False, 0.1, 101), (True, 1.1, 51)):
        times = [stream(stall) for _ in range(4)]
        gaps = [g for t in times for g in stats.token_gaps(t, 0.0, 10.0)]
        assert stats.percentile(gaps, 50) == pytest.approx(0.1)
        assert stats.percentile(gaps, 95) == pytest.approx(p95)
        rate = stats.rate([x for t in times for x in t], 0.0, 10.0)
        assert rate == pytest.approx(4 * per_req / 10)
    assert stats.percentile([], 90) is None


CONF = {"model_type": "qwen2", "num_hidden_layers": 2, "hidden_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "intermediate_size": 16, "vocab_size": 10,
        "torch_dtype": "bfloat16", "rope_theta": 1e4, "rms_norm_eps": 1e-6}
QWEN2 = discover.arch("qwen2")
W = discover.Widths.of(CONF, QWEN2)


def test_decode_attn_work_by_hand():
    s = StepWork(0, 1, decode_rows=[3, 5])
    # flops: 4 * (4*2) * (3+5) per layer = 256, x2 layers
    # elements: 2*(2*2)*n + 2*(4*2) per row = 8n + 16 -> 40 + 56 = 96
    assert decode_attn.count(W, s) == (512.0, 96 * 2 * 2.0)


def test_prefill_chunk_work_by_hand():
    s = StepWork(0, 1, chunks=[(4, 2)])
    # causal keys: 2*4 + 2*3/2 = 11; flops 4*8*11 = 352 per layer
    # elements: 2*4*(4+2) + 2*8*2 = 48 + 32 = 80 per layer
    assert prefill_chunk.count(W, s) == (704.0, 80 * 2 * 2.0)


def test_pool_step_work_by_hand():
    s = StepWork(0, 1, words_written=3, words_read=10, words_scrubbed=8)
    # word: 2 layers * 2 (K,V) * 2 kv heads * 2 dims * 2 bytes = 32 bytes
    assert pool_step.count(W, s) == (0.0, 21 * 32.0)


def test_model_step_work_by_hand():
    s = StepWork(0, 1, decode_rows=[3], chunks=[(0, 2)], out_tokens=1)
    dense = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)   # 2 layers
    assert QWEN2.matmul_params(CONF) == W.matmul_params == dense
    flops = (2 * dense * 3 + 2 * 8 * 10 * 1
             + decode_attn.count(W, s)[0] + prefill_chunk.count(W, s)[0])
    assert model_step.count(W, s) == (float(flops), 0.0)
