"""JAX's persistent compilation cache, placed from outside the program.

Every entry point that compiles a served model (``launch/server.py``,
``launch/serve.py``, ``chipbench/``) calls ``enable_compile_cache``
before its first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it — nothing
  else is configured in code, so the environment alone decides;
* otherwise the cache lives at the fixed ``.jax_cache/`` inside the
  checkout, so each run reads what the last one wrote; a path that
  moved between runs (a temp, pid or time directory) would never hit.

Importing this module or calling the helper initializes no JAX backend.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "checkout_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the checkout root is the directory
    that holds ``src/repro``."""
    root = pathlib.Path(__file__).resolve().parents[3]
    return str(root / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory: the environment's, if set, else ``checkout_cache_dir``."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
