"""Where this program keeps JAX's persistent compilation cache.

One rule for every entry point that compiles (`chip_smoke.py`,
`perfbench/run.py`, the replica child process, the test harness): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other path; otherwise the cache lives at one fixed,
git-ignored directory inside the checkout. A cache directory that moves
is never found again, so the fallback is never derived from a temp name,
a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory. Every compile is kept, however small or fast:
    the suite and the smoke run re-pay hundreds of sub-second compiles
    whose sum is minutes."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
