"""Placement of JAX's persistent compilation cache.

Every cold process on the chip pays every compile unless the compiled
programs persist. The cache directory is part of what the caller's
environment decides: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and this module sets NOTHING (an operator or a harness that
places the cache must not be overridden from code). Where it is not set,
the cache goes to one fixed directory inside the checkout — never to a
temporary name, a process id or a timestamp, because a cache that moves
never hits.

Entry points (``chip_smoke.py``, ``chipbench.run``, the example mains) call
:func:`enable_compile_cache` before their first compile. Library code and
the test suite do not.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory in
    use. Call before the first compile of the process."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # JAX reads the variable; set no directory in code
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
