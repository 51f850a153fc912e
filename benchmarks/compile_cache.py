"""Where the repo's scripts keep JAX's persistent compilation cache.

A chip run compiles the fused drivers and every Pallas kernel of the
active ladder; the cache lets a second run skip that.
The library itself (``import repro``) sets no cache: only the scripts
(``chip_smoke.py``, ``benchmarks/run.py``, ``benchmarks/scale.py``) call
:func:`enable_compile_cache`.
"""
from __future__ import annotations

import os
import re

# fixed, resolved from this file: the cache key includes nothing that
# moves between runs in the same checkout (no temp name, pid or time)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    no other directory is set; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: the kernels of the active ladder compile in
    # about a second each, under JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A compiled Pallas kernel carries its source locations into the key.
    # Keep them to the kernel's own lines, relative to the checkout: an
    # entry is then found again from another checkout of the same code and
    # after edits to the code that calls the kernel.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    return path
