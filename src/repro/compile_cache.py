"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``) call
:func:`enable_compile_cache` once, before their first compile; importing
this module does nothing, so ``import repro`` stays free of jax.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set here.
* Unset: the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed, never derived from a temporary
  name, a pid or the time, so a later run of the same checkout finds what
  an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
