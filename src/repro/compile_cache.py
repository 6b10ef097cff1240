"""Where JAX keeps its persistent compilation cache for this repo's scripts.

A cold run of the scheduler compiles every engine program (the XLA scan
engines take tens of seconds each at real cluster sizes), so the entry
scripts (``chip_smoke.py``, ``benchmarks/``) share JAX's persistent
compilation cache.  The cache key includes its directory, so the directory
must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here touches the configuration;
  * otherwise the cache lives at the fixed path ``<checkout>/.jax_cache``
    (git-ignored).
"""
from __future__ import annotations

import os

import jax

#: The in-checkout cache directory used when JAX_COMPILATION_CACHE_DIR is
#: unset: ``<checkout>/.jax_cache`` (this file is ``src/repro/...``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
