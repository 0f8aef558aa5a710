"""Persistent XLA compilation cache for the entry points.

Compiling the sweep programs takes seconds to minutes per shape, and each
process on a fresh machine would otherwise pay it again.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets nothing.  Otherwise the cache lives in ``<checkout>/.jax_cache`` — a
fixed path, never a temporary or per-process one, so a later process in
the same checkout finds what an earlier one compiled.

Entry points call ``enable_compile_cache`` (``chip_smoke.py``,
``benchmarks.run``, ``repro.launch.train``, ``repro.launch.serve``); the
test suite never does.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
