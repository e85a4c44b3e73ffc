"""JAX's persistent compilation cache for the scripts that run the FFT.

Called by ``chip_smoke.py`` and the examples, never on ``import repro``: a
library does not choose where its users' compiles are kept.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored); a fixed path, because
#: the directory is part of what a later run must find again
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory and no other is set; otherwise the cache is
    :data:`CHECKOUT_CACHE`.  Every compile is kept, however short: an eager
    transform compiles many small programs, none of which reaches JAX's
    default one-second threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
