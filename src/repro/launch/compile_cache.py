"""Persistent XLA compilation cache placement for the entry points.

Every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``) calls ``setup_compile_cache()`` before it compiles
anything.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; the cache
    stays where it points and nothing is set in code.
  * unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path
    (it is part of the cache key, so a path made from a temporary name, a
    process id or the time would never hit), listed in ``.gitignore``.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def setup_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
