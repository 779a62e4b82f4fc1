"""The chip smoke script refuses to run without a TPU, and the entry
points' compile cache lives at a fixed path."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_on_cpu_naming_the_platform():
    got = _run_smoke(ROOT, ROOT / "chip_smoke.py")
    assert got.returncode != 0
    assert "'cpu'" in got.stderr and "TPU" in got.stderr
    assert '"ok"' not in got.stdout


def test_chip_smoke_fails_alone(tmp_path):
    """A directory holding the script and nothing else of the repo."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    got = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert got.returncode != 0
    assert '"ok"' not in got.stdout


def test_compile_cache_dir(monkeypatch):
    from repro.launch.serve import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
