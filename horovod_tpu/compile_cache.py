"""Where the persistent XLA compilation cache lives — the one place.

`chip_smoke.py`, `bench.py` and the `benchmarks/` scripts all call
:func:`enable_compile_cache` before their first compile. The directory is
part of every cache key, so it is placed from OUTSIDE when the
environment says so and at one fixed path otherwise; it is never derived
from a temp dir, a pid or the clock (a directory that moves never hits).
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, no directory setting is
    touched — JAX reads the variable itself. Unset, the cache is
    ``<checkout>/.jax_cache`` (git-ignored). Either way the size/time
    thresholds drop to zero so even cheap programs (init functions,
    the engine's pack/unpack) persist.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
