"""Persistent compilation cache placement for the entry points.

Called from ``main`` of each entry point (``chip_smoke.py``,
``repro.launch.embed``, ``benchmarks/run.py``), never at import: a
library import must not change where a host program caches.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIRNAME = ".jax_cache"


def enable(root) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<root>/.jax_cache``
    (git-ignored): a fixed path, since the directory is part of what a
    later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
