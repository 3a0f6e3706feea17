"""The one persistent compilation cache.

Every entry point (the trainer, the server, ``cellbench``,
``chip_smoke.py``, ``__graft_entry__`` and the test suite) calls
:func:`enable_compile_cache` before its first compile, so a program
compiled by one is found again by the next.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing here touches the setting — whoever runs the program
places the cache.  Otherwise the cache is ``<checkout>/.jax_cache``
(git-ignored): a fixed path inside the tree, because the directory is
part of the cache key's environment and a path that moves (``~``, a
temp name, a pid) never hits.
"""

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
