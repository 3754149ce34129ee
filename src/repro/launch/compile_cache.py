"""Persistent JAX compilation cache for the entry points.

`enable_compile_cache()` is called from the `main` of each entry point
(`chip_smoke.py`, `launch/cluster_serve.py`, `benchmarks/run.py`,
`examples/quickstart.py`), never at import, so importing the library
leaves JAX's configuration alone.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
nowhere else.  Otherwise it lives at a fixed path inside the checkout
(`CACHE_DIR`, listed in `.gitignore`): the path is part of what makes a
later run find the entries, so it is never built from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only those that took over a second: the
    # Pallas kernels compile fast but a cold chip run compiles dozens.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
