"""The persistent compilation cache lands where the environment says, or
at the checkout's fixed ``.jax_cache``; no other path is set in code."""

import os
import subprocess
import sys
from pathlib import Path

from repro.utils.compile_cache import CHECKOUT, DEFAULT_DIR

_PROBE = """
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _probe(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT / "src"))
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, text=True,
                         capture_output=True, check=True, timeout=300)
    return out.stdout.split()


def test_default_is_the_checkout_cache():
    assert DEFAULT_DIR == CHECKOUT / ".jax_cache"
    assert (CHECKOUT / "chip_smoke.py").exists()
    # A zero compile-time floor would write this probe into the checkout's
    # cache; the default floor (1 s) keeps the toy program out of it.
    returned, configured = _probe({})
    assert returned == configured == str(DEFAULT_DIR)


def test_environment_directory_is_left_alone(tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets nothing
    and the entries land there."""
    returned, configured = _probe({
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert returned == configured == str(tmp_path)
    assert any(Path(tmp_path).iterdir())
