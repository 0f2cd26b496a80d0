"""JAX's persistent compilation cache for the entry points.

A launcher calls :func:`use_compile_cache` first thing in its ``main``
(never at import), so a second run of the same program on the same
backend loads its compiled executables instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
cache and no other path is set (JAX reads the variable as it starts;
the helper passes it on again for one set after that).  Otherwise the
cache goes to :data:`DEFAULT_DIR`, a fixed path inside the checkout
(git-ignored).  The path is part of what JAX keys entries on, so it is
never temporary, per-process or per-run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
