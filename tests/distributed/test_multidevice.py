"""Multi-device tests: run in subprocesses with 8 forced host devices so the
main pytest process keeps its single real CPU device."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_py(body: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import registry
        from repro.data.pipeline import DataConfig, ShardedLoader
        from repro.distributed import sharding as shd
        from repro.launch import specs as SP
        from repro.models import init_params
        from repro.train.train_step import TrainConfig, init_train_state, make_train_step

        cfg = registry.get("tinyllama-1.1b", reduced=True)
        tcfg = TrainConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50)
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params, tcfg)
        loader = ShardedLoader(cfg, DataConfig(seed=1), batch=8, seq=16)
        batch = loader.get(0)
        step = make_train_step(cfg, tcfg)

        # single-device result
        s1, m1 = jax.jit(step)(state, batch)

        # sharded result on (2, 4) mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules = shd.Rules.for_mesh(mesh)
        st_shapes = jax.eval_shape(lambda: state)
        st_specs = SP.train_state_pspecs(cfg, mesh, rules, st_shapes)
        bspecs = shd.batch_specs(cfg, mesh, rules, global_batch=8)
        with jax.set_mesh(mesh):
            jf = jax.jit(step,
                         in_shardings=(SP.named_tree(mesh, st_specs),
                                       SP.named_tree(mesh, bspecs)),
                         out_shardings=(SP.named_tree(mesh, st_specs), None))
            s2, m2 = jf(state, batch)
        np.testing.assert_allclose(float(m1["ce"]), float(m2["ce"]), rtol=1e-5)
        d1 = jax.device_get(s1["params"]["lm_head"]["w"])
        d2 = jax.device_get(s2["params"]["lm_head"]["w"])
        np.testing.assert_allclose(d1, d2, atol=2e-5, rtol=1e-4)
        print("SHARDED-OK")
    """)
    assert "SHARDED-OK" in out


def test_grad_compression_close_to_exact_and_ef_accumulates():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import (compressed_mean_pods,
                                                   init_ef_state)
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(2, 64, 33)) * 1e-3, jnp.float32)
        ef = jnp.zeros((2, 64, 33), jnp.float32)
        mean, resid = compressed_mean_pods(g, ef)
        exact = np.asarray(g).mean(0)
        # int8 with per-256 block scales: relative error small
        err = np.abs(np.asarray(mean) - exact).max()
        scale = np.abs(exact).max()
        assert err < 0.03 * scale + 1e-6, (err, scale)
        # error feedback: residual equals quantization error exactly
        # and, summed over steps of a CONSTANT gradient, the running mean of
        # dequantized values converges to the true mean
        acc = np.zeros_like(exact)
        ef_ = jnp.zeros_like(ef)
        for i in range(30):
            m, ef_ = compressed_mean_pods(g, ef_)
            acc += np.asarray(m)
        drift = np.abs(acc / 30 - exact).max()
        assert drift < 2e-3 * scale + 1e-7, drift
        print("COMPRESS-OK")
    """)
    assert "COMPRESS-OK" in out


def test_compressed_train_step_converges_and_int8_on_wire():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import registry
        from repro.data.pipeline import DataConfig, ShardedLoader
        from repro.distributed import sharding as shd
        from repro.launch import specs as SP
        from repro.models import init_params
        from repro.train.train_step import TrainConfig, init_train_state, make_train_step

        from repro.optim import AdamWConfig
        cfg = registry.get("tinyllama-1.1b", reduced=True)
        tcfg = TrainConfig(peak_lr=3e-3, warmup_steps=3, total_steps=60,
                           adamw=AdamWConfig(weight_decay=0.0),
                           grad_compression="int8_ef", n_pods=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params, tcfg)
        loader = ShardedLoader(cfg, DataConfig(seed=2), batch=8, seq=16)
        step = make_train_step(cfg, tcfg)

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 3)
        rules = shd.Rules.for_mesh(mesh)
        st_shapes = jax.eval_shape(lambda: state)
        st_specs = SP.train_state_pspecs(cfg, mesh, rules, st_shapes)
        bspecs = shd.batch_specs(cfg, mesh, rules, global_batch=8)
        state = jax.device_put(state, SP.named_tree(mesh, st_specs))
        bshard = SP.named_tree(mesh, bspecs)
        with jax.set_mesh(mesh):
            jf = jax.jit(step, in_shardings=(SP.named_tree(mesh, st_specs),
                                             SP.named_tree(mesh, bspecs)),
                         out_shardings=(SP.named_tree(mesh, st_specs), None))
            lowered = jf.lower(state, loader.get(0))
            txt = lowered.compile().as_text()
            assert "s8[" in txt, "int8 wire format missing from HLO"
            losses = []
            for i in range(40):
                batch = {k: jax.device_put(v, bshard[k])
                         for k, v in loader.get(i).items()}
                state, m = jf(state, batch)
                losses.append(float(m["ce"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses
        print("COMPRESSED-TRAIN-OK")
    """)
    assert "COMPRESSED-TRAIN-OK" in out


def test_elastic_reshard_between_meshes():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.checkpoint import ckpt
        from repro.distributed import sharding as shd
        from repro.distributed.elastic import reshard_tree
        from repro.launch import specs as SP
        from repro.models import init_params
        from repro.train.train_step import TrainConfig, init_train_state, make_train_step
        from repro.data.pipeline import DataConfig, ShardedLoader

        cfg = registry.get("tinyllama-1.1b", reduced=True)
        tcfg = TrainConfig(peak_lr=1e-3, warmup_steps=2, total_steps=50)
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params, tcfg)
        loader = ShardedLoader(cfg, DataConfig(seed=1), batch=8, seq=16)
        step = make_train_step(cfg, tcfg)

        mesh8 = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules8 = shd.Rules.for_mesh(mesh8)
        st_shapes = jax.eval_shape(lambda: state)
        specs8 = SP.train_state_pspecs(cfg, mesh8, rules8, st_shapes)
        state8 = jax.device_put(state, SP.named_tree(mesh8, specs8))
        with jax.set_mesh(mesh8):
            jf8 = jax.jit(step, in_shardings=(SP.named_tree(mesh8, specs8), None),
                          out_shardings=(SP.named_tree(mesh8, specs8), None))
            s8, _ = jf8(state8, loader.get(0))
        ckpt.save("/tmp/elastic_ck", 0, s8)

        # "pod loss": restart on a 4-device mesh, restore + reshard
        mesh4 = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rules4 = shd.Rules.for_mesh(mesh4)
        specs4 = SP.train_state_pspecs(cfg, mesh4, rules4, st_shapes)
        restored, _ = ckpt.restore("/tmp/elastic_ck", st_shapes,
                                   shardings=SP.named_tree(mesh4, specs4))
        with jax.set_mesh(mesh4):
            jf4 = jax.jit(step, in_shardings=(SP.named_tree(mesh4, specs4), None),
                          out_shardings=(SP.named_tree(mesh4, specs4), None))
            s4, m4 = jf4(restored, loader.get(1))

        # reference: continue on the 8-device mesh
        with jax.set_mesh(mesh8):
            s8b, m8 = jf8(s8, loader.get(1))
        np.testing.assert_allclose(float(m4["ce"]), float(m8["ce"]), rtol=1e-5)
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out
