"""Where JAX keeps its persistent compilation cache.

``enable()`` is called by the entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``launch/train.py``), never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing is
set here; otherwise the cache goes to ``<repo>/.jax_cache`` — a fixed path,
since the directory is part of what a later process must find again.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
