"""Persistent XLA compilation cache shared by the entry points.

A full-width decode step or prefill takes tens of seconds to compile on a
TPU. ``enable_compile_cache()`` makes a second run of ``chip_smoke.py``,
``python -m repro.launch.serve`` or ``examples/serve_sparse.py`` read the
programs the first run compiled:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so
  nothing is set and nothing is written under the checkout;
* unset: the cache lives at ``<repo>/.jax_cache`` — a fixed path, never
  built from a temp name, pid or time, so every run finds it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory. Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
