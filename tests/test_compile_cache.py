"""The persistent compile cache goes where the environment says, and
otherwise to one fixed, git-ignored path inside the checkout."""
import os
import pathlib
import subprocess
import sys

from tests.util import _repo_root

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(path)
print(jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()
"""


def _probe(env, compile_):
    env = dict(env, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)],
        capture_output=True, text=True, check=True, cwd=_repo_root(),
        env=env, timeout=300).stdout.split()
    return out[0], out[1]


def test_cache_dir_from_environment_is_used(tmp_path):
    env = {k: v for k, v in os.environ.items()}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    path, configured = _probe(env, compile_=True)
    assert path == configured == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_cache_dir_defaults_to_fixed_ignored_path_in_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    path, configured = _probe(env, compile_=False)
    root = pathlib.Path(_repo_root())
    assert path == configured == str(root / ".jax_cache")
    ignored = (root / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
