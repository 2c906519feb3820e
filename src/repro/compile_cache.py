"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``examples/serve_decode.py``) call ``use_compile_cache()`` at the start
of ``main``; importing this module changes nothing.  Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored).  A fixed path: a cache directory
# that moves between runs never hits.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this function changes nothing.  Otherwise the cache goes to
    ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
