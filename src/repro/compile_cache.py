"""Persistent XLA compilation cache for the entry points.

Called once at the start of each entry point (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``, ``benchmarks.run``);
never at library import and never from the tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache lives at ``<repo root>/.jax_cache``: a
fixed path, so a second run of the same program finds what the first one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> None:
    """Turn the persistent cache on, where the environment has not."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
