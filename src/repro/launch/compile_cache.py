"""One place that decides where JAX keeps its persistent compilation cache.

A compile is only found again at the directory it was written to, so the
cache pays off at a path that never moves: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and nothing
is set in code), otherwise ``.jax_cache/`` at the root of this checkout
(listed in ``.gitignore``).
Never a temporary directory, a pid or a time stamp.

Call :func:`enable_compile_cache` from an entry point, before the first
compile: ``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks/run.py``
do.  It changes JAX's configuration only and starts no backend.
"""

from __future__ import annotations

import os

__all__ = ["checkout_cache_dir", "enable_compile_cache"]


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (this file is ``src/repro/launch/``)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(here))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
