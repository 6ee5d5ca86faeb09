"""Where JAX's persistent compilation cache lives — ONE placement rule.

Every entry point that compiles at scale (``chip_smoke.py``, ``bench.py``)
calls :func:`place_compile_cache` before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX reads it itself and this module
  sets no directory in code (whoever runs the program owns the location);
- otherwise → ``<checkout>/.jax_cache`` (git-ignored). The path is part of
  the cache key's environment, so it is fixed, never a temp dir.

Config updates only: nothing here initializes a backend, so a launcher
parent may call it and still leave the chip to its children.
"""

from __future__ import annotations

import os

#: the variable JAX itself reads for ``jax_compilation_cache_dir``
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache(checkout: str) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile is kept, however short (the default 1 s floor would make
    a second run recompile each small program it compiled before)."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries(path: str) -> int:
    """Files in the cache directory (0 when it does not exist yet)."""
    try:
        return len(os.listdir(path))
    except OSError:
        return 0
