"""The persistent compile cache is placed from outside the program:
``$JAX_COMPILATION_CACHE_DIR`` when set, a fixed in-checkout path otherwise.

Each case runs in a subprocess: the helper sets process-wide JAX config."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
path = use_compile_cache()
jax.jit(lambda x: jnp.cos(x) + 3)(jnp.ones(5)).block_until_ready()
print(path)
print(len([f for f in os.listdir(path) if f.endswith("-cache")]))
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                       text=True, timeout=120, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    path, n_entries = r.stdout.split()[-2:]
    return path, int(n_entries)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(tmp_path, from_env):
    path, n_entries = _probe(tmp_path / "jax-cache" if from_env else None)
    if from_env:
        assert path == str(tmp_path / "jax-cache")
    else:
        assert path == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    assert n_entries > 0
