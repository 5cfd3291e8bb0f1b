"""Where JAX keeps compiled programs between runs.

Every entry point (``chip_smoke.py``, the examples, the benchmark and
launch CLIs) calls :func:`use_compile_cache` before it compiles anything,
so one run finds what an earlier run on the same machine compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it
    itself, and nothing here overrides it.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, since the path is part of
    what a later run must match."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
