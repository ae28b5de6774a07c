"""JAX's persistent compilation cache, placed for the program's entry points.

Called from entry points only (``serve.main``, ``chip_smoke.py``, the
benchmark mains), never on import, so tests and library users keep JAX's
own defaults.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here names a directory.  Otherwise the cache lives at
a fixed ``.jax_cache/`` in the checkout: the path is part of the cache's
key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional, Tuple

#: The environment variable JAX itself reads for the cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fallback cache directory: ``.jax_cache/`` at the checkout root.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: Compiles at least this long are cached (JAX's default is 1 s, which
#: drops most kernel compiles).
MIN_COMPILE_SECS = 0.5


def cache_dir(environ: Optional[Mapping[str, str]] = None
              ) -> Tuple[str, bool]:
    """``(directory, from_env)``: the environment's directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else :data:`DEFAULT_DIR`."""
    env = os.environ if environ is None else environ
    if env.get(CACHE_ENV):
        return env[CACHE_ENV], True
    return str(DEFAULT_DIR), False


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    import jax
    path, from_env = cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
