"""Persistent XLA compilation cache: placed from outside, or in the checkout.

Compiling the sweep, engine and trainer programs is a large part of a
cold run, so every process keeps JAX's persistent compilation cache on
and pays each compile once per cache directory instead of once per
process.  The directory is fixed, because the path is part of what JAX
finds again:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set.  JAX reads that variable
  itself, and this module then sets no directory at all.
* otherwise :data:`CHECKOUT_DIR`, ``.jax_cache`` at the root of the
  checkout (gitignored).

:func:`enable_compile_cache` applies the rule; ``import repro.sim`` calls
it.  JAX's own thresholds stay in force (by default only programs that
take at least a second to compile are written), and
``jax_enable_compilation_cache`` still turns the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the environment variable JAX reads its cache directory from.
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the cache directory when ``$JAX_COMPILATION_CACHE_DIR`` is not set.
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the cache directory of the rule above; returns it.

    Only programs compiled afterwards are cached, so this runs when
    ``repro.sim`` is imported, before the first jitted call."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
