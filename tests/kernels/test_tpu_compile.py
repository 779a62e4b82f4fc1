"""Compile the served path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at tinyllama-1.1b widths (32/4
heads, head_dim 64, 22 layers in the pool word) in the dtypes the engine
feeds it — bf16 queries, float32 staged caches and pool — and compiles it
for one chip of a ``v5e:2x2`` topology that is described, not attached.
The TPU compiler then refuses what interpret mode cannot see: unaligned
slices, scalar stores to vector memory, more VMEM than a kernel may use.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import PortConfig, PortRequest
from repro.core.multiport import MemorySpec, step_banked
from repro.kernels import kv_multiport as kvmp
from repro.kernels import kv_prefill_chunk as kvpc
from repro.kernels.multiport_sram import bank_count
from repro.kernels.tiling import word_pad
from repro.memory.paged_kv import _PRIORITY, _ROLES

B, S, C = 8, 2048, 64              # decode rows, staged capacity, chunk rows
SEQ_TILE = 64
POOL_WORDS, LANES = 4096, 2048     # 8 slots x 512 tokens; decode-read lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for an unattached chip is written to the
        # persistent cache but cannot be read back: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def cfg():
    return registry.get("tinyllama-1.1b")


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("splits", [1, 4])
def test_decode_kernel_compiles(sds, cfg, splits):
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    fn = functools.partial(kvmp.fused_append_attend, seq_tile=SEQ_TILE,
                           dynamic_grid=True, num_kv_splits=splits,
                           interpret=False)
    text = _compile(fn, sds((B, h, d), jnp.bfloat16),
                    sds((B, S, hkv, d), jnp.float32),
                    sds((B, S, hkv, d), jnp.float32),
                    sds((B, hkv, d), jnp.float32),
                    sds((B, hkv, d), jnp.float32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_prefill_chunk_kernel_compiles(sds, cfg):
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    fn = functools.partial(kvpc.fused_chunk_append_attend, seq_tile=SEQ_TILE,
                           dynamic_grid=True, interpret=False)
    text = _compile(fn, sds((B, C, h, d), jnp.bfloat16),
                    sds((B, S, hkv, d), jnp.float32),
                    sds((B, S, hkv, d), jnp.float32),
                    sds((B, C, hkv, d), jnp.float32),
                    sds((B, C, hkv, d), jnp.float32),
                    sds((B,), jnp.int32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_pool_step_kernel_compiles(sds, cfg):
    width = word_pad(cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim_)
    spec = MemorySpec(num_words=POOL_WORDS, word_width=width,
                      num_banks=bank_count(POOL_WORDS, width * 4))
    port_cfg = PortConfig(enabled=(True,) * 4, roles=_ROLES,
                          priority=_PRIORITY)
    req = PortRequest(addr=sds((LANES,), jnp.int32),
                      data=sds((LANES, width), jnp.float32),
                      mask=sds((LANES,), jnp.bool_))
    fn = lambda st, reqs: step_banked(spec, port_cfg, st, reqs,  # noqa: E731
                                      interpret=False)
    text = _compile(fn, sds((POOL_WORDS, width), jnp.float32), (req,) * 4)
    assert "tpu_custom_call" in text
