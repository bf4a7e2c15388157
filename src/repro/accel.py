"""Accelerator set-up shared by the Pallas kernels and the entry points."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache key includes it, so a moving directory never hits
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_interpret() -> bool:
    """Pallas kernels compile on a TPU backend; on any other backend (the
    CPU test suite) they run in the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set here; otherwise the cache lives in ``<checkout>/.jax_cache``.
    Returns the directory in use.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
