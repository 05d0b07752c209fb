"""Where JAX keeps its persistent compilation cache.

Called once at the start of every entry point that compiles for the chip
(``chip_smoke.py``, ``repro.launch.lda``, ``repro.launch.topic_serve``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing here
  sets another directory.
* Unset: the cache goes to ``.jax_cache`` at the root of the checkout.  The
  path is fixed -- never temporary, pid- or time-derived -- because the
  directory is part of what a later run must find again.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
