"""JAX's persistent compilation cache, placed from outside.

Entry points (`launch.train`, `launch.serve`, `chip_smoke.py`) call
`use_compile_cache()` once before they compile.  The tests do not: a
compile for a described (unattached) chip is written to the cache but
cannot be read back without one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is `<checkout>/.jax_cache`:
    a fixed path, so a second process in the same checkout finds what
    the first one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
