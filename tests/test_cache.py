"""Persistent compile cache wiring (ISSUE 5 satellite): enable_compile_cache
points jax at a cache dir by default, a second lowering of the same program
hits the on-disk cache instead of recompiling, and the env opt-out works.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from fedml_tpu.utils.cache import enable_compile_cache


@pytest.fixture
def restore_jax_cache_config():
    """The suite-wide conftest points jax at the repo .jax_cache — put it
    back however this test leaves it. The persistent cache object is
    process-wide and latches the dir it was first used with, so a config
    change only takes effect after reset_cache()."""
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
    cc.reset_cache()


def _cache_files(d):
    return {f for f in os.listdir(d) if not f.startswith(".")}


def test_second_lowering_hits_cache_dir(tmp_path, restore_jax_cache_config):
    d = str(tmp_path / "jcache")
    assert enable_compile_cache(min_compile_secs=0.0, cache_dir=d)
    assert jax.config.jax_compilation_cache_dir == d

    @jax.jit
    def f(x):
        return jnp.tanh(x) @ x.T

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    first = _cache_files(d)
    assert first, "compile produced no persistent cache entries"

    jax.clear_caches()              # force a re-lowering of the same program
    f(x).block_until_ready()
    assert _cache_files(d) == first  # served from disk: no new entries


def test_env_opt_out(tmp_path, restore_jax_cache_config, monkeypatch):
    monkeypatch.setenv("FEDML_TPU_NO_COMPILE_CACHE", "1")
    before = jax.config.jax_compilation_cache_dir
    assert not enable_compile_cache(cache_dir=str(tmp_path / "nope"))
    assert jax.config.jax_compilation_cache_dir == before


def test_env_dir_override(tmp_path, restore_jax_cache_config, monkeypatch):
    """A directory placed from outside stands: jax reads
    JAX_COMPILATION_CACHE_DIR itself (once, at import), so with the variable
    set enable_compile_cache() leaves the directory as jax read it and sets
    no other — only the threshold moves."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "envdir"))
    as_jax_read_it = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(min_compile_secs=0.0)
    assert jax.config.jax_compilation_cache_dir == as_jax_read_it
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_dir_is_read_by_jax_not_set_by_repo(tmp_path):
    """End to end in a fresh interpreter: with the variable set, the
    directory in effect after enable_compile_cache() is the variable's, and
    a compile lands there; unset, it is <checkout>/.jax_cache."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = ("import jax, jax.numpy as jnp\n"
            "from fedml_tpu.utils.cache import enable_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "assert enable_compile_cache(min_compile_secs=0.0)\n"
            "jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((8, 8)))"
            ".block_until_ready()\n"
            "print(before, jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "FEDML_TPU_NO_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    d = str(tmp_path / "outside")
    out = subprocess.run([sys.executable, "-c", prog], check=True, cwd=repo,
                         env={**env, "JAX_COMPILATION_CACHE_DIR": d},
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == [d, d]          # jax read it; nobody reset it
    assert _cache_files(d), "the compile wrote no entry into the outside dir"
    out = subprocess.run([sys.executable, "-c", prog], check=True, cwd=repo,
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == ["None", os.path.join(repo, ".jax_cache")]


def test_default_is_repo_local(restore_jax_cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("FEDML_TPU_NO_COMPILE_CACHE", raising=False)
    assert enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir.endswith(".jax_cache")
