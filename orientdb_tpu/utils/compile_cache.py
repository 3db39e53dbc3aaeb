"""JAX persistent compilation cache, placeable from outside.

Entry points (``python -m orientdb_tpu.server``, ``chip_smoke.py``,
the ``tools`` mains) call :func:`enable_compile_cache` before their
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already honours it and this code sets no directory; where it is not,
the cache goes to ONE fixed path inside the checkout — the path is part
of the cache key, so a directory built from a temp name, pid or time
would never hit. Library imports and the test suite never enable it.
"""

from __future__ import annotations

import os

#: the fixed in-checkout location (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

