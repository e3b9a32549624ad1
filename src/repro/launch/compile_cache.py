"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``repro.launch.serve``, ``repro.launch.serve_cluster``,
``repro.launch.dryrun``, ``chip_smoke.py``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  overrides it.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).  The path is fixed on purpose — a cache
  directory that moves from run to run never hits.

Call :func:`configure_compile_cache` from an entry point's ``main``, never
at import time: it changes process-wide JAX configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
