"""JAX's persistent compilation cache for the command-line entry points.

`enable_compile_cache()` is called by the `__main__` blocks of the
launchers and by `chip_smoke.py` — never at import time and never from
tests (which call the launchers' `main()` directly). When
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing else
is configured here. Otherwise the cache lives at a fixed path inside the
checkout (`<repo>/.jax_cache`, gitignored), so the next process finds
what this one compiled: it is never derived from a temp name, a PID or
the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

_REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return str(_REPO_CACHE)
