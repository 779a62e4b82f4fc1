"""Decode retire reads back one KV word and one token per staged row: the
words the fused engine commits at decoded positions match those of the
two-pass reference engine and those a whole-sequence prefill computes,
with dead rows in the batch, and the greedy tokens are identical."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import init_decode_state, init_params, prefill
from repro.serve.engine import MultiPortEngine


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get("tinyllama-1.1b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    """Four slots, three requests: one row is always dead, and the longest
    prompt prefills over three chunks while the others decode."""
    eng = MultiPortEngine(params, cfg, slots=4, max_len=64, chunk_tokens=8,
                          seq_tile=16, interpret=True, **kw)
    rng = np.random.default_rng(5)
    for n in (5, 11, 19):
        eng.submit(list(rng.integers(0, cfg.vocab, n)), max_new=12)
    return eng


def _prefill_words(cfg, params, tokens):
    """Pool words of ``tokens`` computed by one plain prefill: the oracle
    the decode path's appended words are held to. The prompt is padded to
    the 64 positions of the engine (attention is causal, so the padding
    changes no earlier word)."""
    state = init_decode_state(cfg, 1, 64)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :len(tokens)] = tokens
    state, _ = prefill(params, cfg, state, {"inputs": jnp.asarray(padded)})
    w = np.stack([np.asarray(state["cache_k"][:, 0], np.float32),
                  np.asarray(state["cache_v"][:, 0], np.float32)], axis=1)
    return np.moveaxis(w, 2, 0).reshape(64, -1)[:len(tokens)]


@pytest.mark.parametrize("splits", [1, 2])
def test_retired_words_match_reference(setup, splits):
    cfg, params = setup
    fused = _engine(cfg, params, num_kv_splits=splits)
    ref = _engine(cfg, params, kernel_mode="reference")
    for _ in range(9):
        fused.step()
        ref.step()
    assert fused._fused_compute and fused.num_kv_splits == splits
    assert fused.steady_decode_steps >= 5
    assert fused.slot_len == ref.slot_len
    decoded = 0
    for i, r in enumerate(fused.slot_req):
        if r is None:
            continue
        rr = ref.slot_req[i]
        assert r.generated == rr.generated
        pos = np.arange(len(r.prompt), fused.slot_len[i])
        decoded += pos.size
        got = fused.pool.gather_words(r.rid, pos)
        np.testing.assert_allclose(got, ref.pool.gather_words(rr.rid, pos),
                                   atol=1e-5, rtol=1e-5)
        seq = [int(t) for t in r.prompt + r.generated][:fused.slot_len[i]]
        want = _prefill_words(cfg, params, seq)[pos]
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert decoded >= 8
    # each retire read back one word and one token per staged row
    word = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim_
    fused.flush()
    assert fused._pending
    for w in fused._pending.values():
        assert w.shape == (word,) and w.dtype == np.float32
    done = {r.rid: r.generated for r in fused.run(max_cycles=200)}
    want = {r.rid: r.generated for r in ref.run(max_cycles=200)}
    assert len(done) == 3 and done == want
