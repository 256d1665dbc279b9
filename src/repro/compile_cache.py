"""Where JAX keeps compiled programs between runs.

One helper for every entry point (``python -m repro``,
``benchmarks/run.py``, ``chip_smoke.py``).  Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at one fixed path inside the checkout,
``<repo>/.jax_cache`` (git-ignored): the cache is looked up by path, so a
temp name, a PID or a timestamp would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Call before the first compilation: JAX fixes the cache location when
    it first compiles.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
