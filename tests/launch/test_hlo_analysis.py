"""The HLO analyzer is load-bearing for §Roofline — validate it against
hand-countable programs."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as HA


def _analyze(fn, *args):
    return HA.analyze(jax.jit(fn).lower(*args).compile().as_text())


def test_scan_trip_count_expansion():
    w = jnp.ones((256, 256), jnp.float32)

    def body(c, _):
        return c @ w, None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    def unrolled(x):
        for _ in range(7):
            x = x @ w
        return x

    x = jnp.ones((256, 256), jnp.float32)
    want = 2 * 256**3 * 7
    a, b = _analyze(scanned, x), _analyze(unrolled, x)
    assert a["dot_flops"] == want, a["dot_flops"]
    assert b["dot_flops"] == want, b["dot_flops"]


def test_nested_scan_multiplies():
    w = jnp.ones((128, 128), jnp.float32)

    def inner(c, _):
        return c @ w, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=3)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    a = _analyze(f, jnp.ones((128, 128), jnp.float32))
    assert a["dot_flops"] == 2 * 128**3 * 15, a["dot_flops"]


def test_gqa_einsum_flops():
    # einsum with batch dims: [B,H,S,D] x [B,H,D,S] contraction
    def f(q, k):
        return jnp.einsum("bhsd,bhtd->bhst", q, k)
    q = jnp.ones((2, 4, 64, 32), jnp.float32)
    k = jnp.ones((2, 4, 64, 32), jnp.float32)
    a = _analyze(f, q, k)
    want = 2 * 2 * 4 * 64 * 64 * 32
    assert a["dot_flops"] == want, (a["dot_flops"], want)


def test_slice_counts_window_not_operand():
    big = jnp.ones((4096, 256), jnp.float32)      # 4 MB

    def f(x, i):
        return jax.lax.dynamic_slice(x, (i, 0), (16, 256)) * 2.0

    a = _analyze(f, big, jnp.int32(0))
    # refined traffic must be well under one full read of the operand
    assert a["traffic_bytes"] < big.size * 4 * 0.5, a["traffic_bytes"]
    assert a["traffic_bytes_naive"] >= big.size * 4


def test_dus_counts_update_window():
    big = jnp.zeros((4096, 256), jnp.float32)
    upd = jnp.ones((16, 256), jnp.float32)

    def f(x, u, i):
        return jax.lax.dynamic_update_slice(x, u, (i, 0))

    # donate the target so the in-place update isn't preceded by a copy
    jf = jax.jit(f, donate_argnums=0)
    a = HA.analyze(jf.lower(big, upd, jnp.int32(0)).compile().as_text())
    assert a["traffic_bytes"] < big.size * 4, a["traffic_bytes"]


def test_collectives_counted_with_loop_expansion():
    import os
    import subprocess
    import sys
    import textwrap
    root = __file__.rsplit("/tests/", 1)[0]
    env = dict(os.environ)
    env["PYTHONPATH"] = root + "/src"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo_analysis as HA
        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,) * 1)
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        def f(x, ws):
            y, _ = jax.lax.scan(body, x, ws)
            return y
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        with jax.set_mesh(mesh):
            jf = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P(None, "model")),
                NamedSharding(mesh, P(None, "model", None))))
            a = HA.analyze(jf.lower(x, ws).compile().as_text())
        n = sum(a["collective_counts"].values())
        assert n >= 5, a["collective_counts"]   # one+ per scan iteration
        print("COLL-OK", n)
    """)
    r = subprocess.run([sys.executable, "-c", body], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0 and "COLL-OK" in r.stdout, r.stdout + r.stderr
