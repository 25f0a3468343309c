"""JAX's persistent compilation cache, placed from outside the program.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `python -m
repro.serving`, `python -m repro.launch.train`) call
`enable_compile_cache()` before their first compile, so a rerun on the
same machine reads compiled programs back instead of compiling again.

* Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  this helper sets nothing: the caller's directory wins.
* Otherwise the cache lives at a fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in `.gitignore`). The path is part of
  the cache key, so it is never derived from a temp name, a pid or the
  time.

Nothing here runs at import: the tests never turn the cache on.
"""

from __future__ import annotations

import os
import pathlib

#: `<repo>/.jax_cache`: this file sits at `<repo>/src/repro/launch/`.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
