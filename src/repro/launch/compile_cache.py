"""Persistent XLA compilation cache for the entry points.

Entry points (the launchers, the serving/training benchmarks and
`chip_smoke.py`) call `enable_compile_cache()` before their first compile;
importing this module changes nothing, so tests keep JAX's defaults.

If `JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and this
sets nothing else. Otherwise the cache lives at `<checkout>/.jax_cache`
(git-ignored): a fixed path, because the directory is part of where later
runs look for entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
