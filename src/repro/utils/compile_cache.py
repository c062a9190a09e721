"""Placement of JAX's persistent compilation cache for the entry points.

A compiled program is found again only under the same cache path, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing else is set here), otherwise
``<checkout>/.jax_cache``. Entry points (``chip_smoke.py``,
``repro.launch.serve``, the benchmark mains) call :func:`enable` before
their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
