"""JAX's persistent compilation cache for this repository's entry points.

Entry points (``repro.launch.serve``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once, before their
first compile; importing this module changes nothing. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this code sets
no other directory. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a later run must find again.

A cache inside the checkout holds every program a run compiles: a size cap
from the environment (``JAX_COMPILATION_CACHE_MAX_SIZE``) is lifted for it.
JAX evicts the least recently used entries past the cap, so a run whose
programs exceed it (a 16B expert model's step programs and reference do)
evicts its first programs before the next run reads them, and every run
compiles everything again. A directory outside the checkout keeps its cap.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the repository root (``src/repro/launch/`` is three levels below it)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if pathlib.Path(path).resolve().is_relative_to(CHECKOUT):
        jax.config.update("jax_compilation_cache_max_size", -1)
    return path
