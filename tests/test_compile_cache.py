"""Where the scripts' persistent compilation cache lands.

Each case runs a tiny jitted program in a child process pinned to the CPU,
with ``benchmarks/compile_cache.py`` copied into a scratch checkout, and
looks for the cache entries: only in ``JAX_COMPILATION_CACHE_DIR`` when it
is set, only in ``<checkout>/.jax_cache`` when it is not.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = """
import sys
sys.path.insert(0, sys.argv[1])
from benchmarks.compile_cache import enable_compile_cache
import jax, jax.numpy as jnp
print(enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(checkout, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    out = subprocess.run([sys.executable, "-c", CHILD, str(checkout)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


@pytest.fixture
def checkout(tmp_path):
    co = tmp_path / "checkout"
    (co / "benchmarks").mkdir(parents=True)
    shutil.copy(os.path.join(REPO, "benchmarks", "compile_cache.py"),
                co / "benchmarks" / "compile_cache.py")
    return co


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_lands_in_env_dir_or_checkout(checkout, tmp_path, env_set):
    env_dir = tmp_path / "from_env"
    used = _run(checkout, env_dir if env_set else None)
    own = checkout / ".jax_cache"
    if env_set:
        assert used == str(env_dir)
        assert any(env_dir.iterdir())
        assert not own.exists()
    else:
        assert used == str(own)
        assert any(own.iterdir())
        assert not env_dir.exists()
