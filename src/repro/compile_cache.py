"""Where JAX keeps compiled programs between processes.

A program's entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/check_equivalence.py``) calls
:func:`configure_compile_cache` once, before its first compile; the
library never does, so importing it changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE", "configure_compile_cache"]

#: the cache directory used when the environment names none: a fixed
#: path inside the checkout (listed in .gitignore).  The directory is
#: part of what a later run must find again, so it holds no temporary
#: name, pid or time.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
    and no other directory is set.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
