"""repro.compile_cache: JAX's persistent compilation cache lives in
$JAX_COMPILATION_CACHE_DIR when that is set, and otherwise at the
fixed <repo>/.jax_cache."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import ENV_VAR, REPO_CACHE_DIR, setup_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()


def test_env_var_names_the_cache_dir(monkeypatch, tmp_path,
                                     restore_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert setup_compile_cache() == REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR


def test_compiled_programs_land_in_the_env_dir(tmp_path):
    """End to end, in a fresh process: a compile after the helper runs
    writes its entry under $JAX_COMPILATION_CACHE_DIR."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.compile_cache import setup_compile_cache
        setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", **{ENV_VAR: str(tmp_path)})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert os.listdir(tmp_path), "no compiled program was cached"
