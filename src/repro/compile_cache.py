"""Persistent XLA compilation cache for the program's entry points.

Called from ``chip_smoke.py``, ``python -m repro.serve`` and the
benchmark entry points — never on import, so library users and the
tests keep JAX's own default (no persistent cache).
"""
from __future__ import annotations

import os
import pathlib

# A fixed path inside the checkout: the cache directory is part of what
# a later run has to find again, so it is never derived from a
# temporary name, a process id or the time.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory from the environment and this sets nothing; otherwise the
    cache lives at :data:`DEFAULT_CACHE_DIR`.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
