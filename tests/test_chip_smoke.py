"""chip_smoke.py refuses to run off the TPU and names the programs the
compile cache missed, and the compile-cache helper honours
``JAX_COMPILATION_CACHE_DIR`` before its fixed in-checkout path."""
import importlib.util
import logging
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("args", [[], ["--four-chip"]])
def test_chip_smoke_refuses_cpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                         env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert "needs a TPU" in run.stderr and "'cpu'" in run.stderr
    assert '"ok"' not in run.stdout


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    want = str(ROOT / ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_log_names_missed_and_unwritten_programs(tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    log = logging.getLogger("jax._src.compiler")
    saved = log.level, log.propagate
    before = jax.config.jax_compilation_cache_dir
    cache = chip_smoke.CacheLog()
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))

        def cache_log_probe(x):
            return jnp.tanh(x) * 3.0

        jax.jit(cache_log_probe)(jnp.ones(7)).block_until_ready()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        log.removeHandler(cache)
        log.setLevel(saved[0])
        log.propagate = saved[1]
    assert cache.names["misses"]["jit_cache_log_probe"] == 1
    # compiled in well under the 1 s default, so JAX does not write it
    assert cache.names["not written"]["jit_cache_log_probe"] == 1
    assert "jit_cache_log_probe" in cache.report()
