"""Where the persistent compilation cache goes."""

import os
import subprocess
import sys
import textwrap

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_cache_dir_is_fixed_and_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_ENV_DIR_SCRIPT = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache

    want = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(4)).block_until_ready()
    assert os.listdir(want), "nothing written to JAX_COMPILATION_CACHE_DIR"
""")


def test_env_cache_dir_is_used_and_not_overridden(tmp_path):
    cache = tmp_path / "cache"
    default = os.path.join(REPO, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    proc = subprocess.run(
        [sys.executable, "-c", _ENV_DIR_SCRIPT],
        cwd=REPO,
        env={
            **os.environ,
            "PYTHONPATH": os.path.join(REPO, "src"),
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(cache),
            # cache even a sub-second compile, so the test can see it
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        },
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert after == before, "cache entries written to the default directory"
