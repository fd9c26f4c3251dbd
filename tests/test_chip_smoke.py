"""The chip smoke entry point and the compile-cache helper, off the chip."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_compile_cache_env_var_wins_else_fixed_checkout_dir(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        # the variable is left to JAX itself: nothing is set in code
        assert jax.config.jax_compilation_cache_dir == prev

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        want = os.path.join(os.path.realpath(_ROOT), ".jax_cache")
        assert got == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stdout
