"""Where the persistent XLA compilation cache lives — one rule.

Every entry point of the repo (``chip_smoke.py``, ``bench.py``, the
``tools/`` scripts, ``tests/conftest.py``, ``python -m
coreth_tpu.plugin.run_vm``, ``python -m
coreth_tpu.serve.cluster.worker``) calls :func:`configure` once, before
its first jit:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own reading of the variable
  stands and no code sets another directory, so whoever runs the
  program (a CI driver, the chip tool's machine) places the cache;
- unset: the cache goes to the fixed ``tests/.jax_cache`` inside the
  checkout (gitignored).  Never a temp name, pid or time: the path is
  part of the cache key, so a directory that moves never hits.

Child processes inherit the parent's environment and run the same rule,
so nothing passes the directory down by hand.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO_ROOT, "tests", ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# executables that took less than this to compile are not worth a file
MIN_COMPILE_SECS = 1.0


def configure() -> str:
    """Apply the rule; returns the directory the cache uses."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
