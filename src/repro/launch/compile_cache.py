"""JAX's persistent compilation cache, turned on in one place.

A full-width prefill or decode program takes tens of seconds to compile,
and every distinct prompt length is its own program.  Entry points
(``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable` before their first compile so a
second run of the same programs loads them from disk.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed path inside the
checkout (``artifacts/jax_cache``, git-ignored): the directory is part of
what a run finds again, so it never carries a temp name, a pid or a time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<repo>/artifacts/jax_cache`` (three levels up from this package)."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(root, "artifacts", "jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
