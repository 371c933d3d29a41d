"""The one compile-cache rule.

Every entry point that jits (``serve``, ``search --device``,
``chip_smoke.py``) calls :func:`configure` before its first jit. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set in code; otherwise the persistent cache
lives in ONE fixed directory inside the checkout. The path is part of
every entry's key, so it never carries a pid, a time or a temp name —
a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"


def configure() -> str:
    """Apply the rule; return the directory the cache lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
