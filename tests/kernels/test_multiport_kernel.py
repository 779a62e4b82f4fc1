"""Pallas multiport_sram kernel vs the jnp oracle: shape/dtype sweeps, and
the 1-traversal bandwidth property (claim C1) via cost accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemorySpec, PortConfig, READ, WRITE, PortRequest, step
from repro.kernels import ops


def _random_case(rng, spec, q, roles):
    reqs = []
    for p in range(4):
        addr = rng.integers(0, spec.num_words, q)
        data = rng.normal(size=(q, spec.word_width)).astype(np.float32)
        mask = rng.random(q) > 0.25
        reqs.append(PortRequest(addr=jnp.asarray(addr, jnp.int32),
                                data=jnp.asarray(data, spec.dtype),
                                mask=jnp.asarray(mask)))
    storage = jnp.asarray(
        rng.normal(size=(spec.num_words, spec.word_width)), spec.dtype)
    return storage, reqs


@pytest.mark.parametrize("num_words,width,banks,q", [
    (32, 4, 4, 4),
    (64, 8, 8, 16),
    (128, 16, 4, 32),
    (64, 4, 1, 8),        # single bank edge case
    (64, 4, 64, 8),       # one word per bank
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_vs_oracle_sweep(rng, num_words, width, banks, q, dtype):
    spec = MemorySpec(num_words=num_words, word_width=width, num_banks=banks,
                      dtype=dtype)
    cfg = PortConfig(enabled=(True, True, True, True),
                     roles=(WRITE, READ, WRITE, READ))
    storage, reqs = _random_case(rng, spec, q, cfg.roles)
    s_ref, r_ref = step(spec, cfg, storage, reqs)
    s_k, r_k = ops.multiport_step(spec, cfg, storage, reqs, interpret=True)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s_k, np.float32),
                               np.asarray(s_ref, np.float32), atol=tol)
    for p in range(4):
        np.testing.assert_allclose(np.asarray(r_k[p], np.float32),
                                   np.asarray(r_ref[p], np.float32), atol=tol)


@pytest.mark.parametrize("n_ports", [1, 2, 3, 4])
def test_kernel_port_count_configs(rng, n_ports):
    spec = MemorySpec(num_words=64, word_width=4, num_banks=8)
    roles = (WRITE, READ, READ, WRITE)
    cfg = PortConfig(enabled=tuple(i < n_ports for i in range(4)), roles=roles)
    storage, reqs = _random_case(rng, spec, 8, roles)
    s_ref, r_ref = step(spec, cfg, storage, reqs)
    s_k, r_k = ops.multiport_step(spec, cfg, storage, reqs, interpret=True)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), atol=1e-6)
    for p in range(4):
        np.testing.assert_allclose(np.asarray(r_k[p]), np.asarray(r_ref[p]),
                                   atol=1e-6)


def test_one_traversal_regardless_of_port_count():
    """C1: kernel HBM traffic over the storage is ~constant in the enabled
    port count, while the single-port baseline's scales linearly."""
    spec = MemorySpec(num_words=512, word_width=8, num_banks=8)
    q = 16

    def kernel_storage_bytes(n_ports):
        cfg = PortConfig(enabled=tuple(i < n_ports for i in range(4)),
                         roles=(WRITE, READ, WRITE, READ))
        rng = np.random.default_rng(0)
        storage, reqs = _random_case(rng, spec, q, cfg.roles)
        f = jax.jit(lambda s, r: ops.multiport_step(spec, cfg, s, r,
                                                    interpret=True))
        lowered = f.lower(storage, reqs)
        cost = lowered.compile().cost_analysis()
        return cost.get("bytes accessed", 0.0)

    b1, b4 = kernel_storage_bytes(1), kernel_storage_bytes(4)
    # storage dominates the traffic; ports add only queue-sized metadata
    assert b4 < 1.6 * b1, (b1, b4)
