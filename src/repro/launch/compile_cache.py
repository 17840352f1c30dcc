"""Where JAX keeps its persistent compilation cache.

A served model compiles its decode and prefill steps once per process;
the persistent cache lets the next process on the same machine load them
instead. Call ``use_compile_cache()`` from an entry point, never at
import time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: JAX reads this variable itself; when it is set, nothing here moves it
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: fixed default: the directory is part of every cache key, so it must
#: not depend on a temporary name, a pid or the time
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
