"""Where JAX's persistent compile cache lands: in
``JAX_COMPILATION_CACHE_DIR`` when it is set (and nowhere else),
otherwise in the fixed ``<repo>/.jax_cache``."""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).parent.parent

_PROBE = """
import pathlib, sys
from ceph_tpu.utils import arch
arch._REPO = pathlib.Path(sys.argv[1])
print(arch.configure_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 3 + 1)(jnp.arange(16)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_placement(tmp_path, env_set):
    repo, env_dir = tmp_path / "repo", tmp_path / "env_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _PROBE, str(repo)], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    want, other = ((env_dir, repo / ".jax_cache") if env_set
                   else (repo / ".jax_cache", env_dir))
    assert r.stdout.split()[0] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
    # nothing else was written beside it (no temp or pid-named cache)
    assert [p.name for p in tmp_path.iterdir()] == [
        want.relative_to(tmp_path).parts[0]]
