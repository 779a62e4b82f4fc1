"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_kv_mesh(n_shards: int, *, axis: str = "kv", devices=None):
    """1-D ``kv`` mesh over the first ``n_shards`` devices — the serving
    engine's data-parallel-KV surface (paged pool sharded page-aligned on
    its word axis; staged kernel batches sharded by home device). Built as
    a plain ``Mesh`` (no axis types): the pool and the fused kernels enter
    it through explicit ``shard_map``, never an ambient-mesh jit.

    On CPU CI, force host devices BEFORE the first jax import:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n_shards > len(devices):
        raise ValueError(
            f"kv mesh needs {n_shards} devices but only {len(devices)} are "
            f"visible — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} before jax "
            f"initializes")
    return Mesh(np.array(devices[:n_shards]), (axis,))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
