"""Where JAX keeps its persistent compilation cache.

A cache only hits when its directory stays put between runs, so the default
is a fixed directory inside the checkout (`<repo>/.jax_cache`, gitignored),
never a temporary or per-process name. Where `JAX_COMPILATION_CACHE_DIR` is
set, that directory is the only one used.

Call `enable_compile_cache()` at the start of an entry point, before the
first compilation.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `JAX_COMPILATION_CACHE_DIR`
    if it is set, else at `DEFAULT_DIR`; returns the directory."""
    import jax
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
