"""Where the entry points keep JAX's persistent compilation cache.

A cold run compiles every program of the simulator; on a TPU that is a
large share of a short run.  A cache directory that moves between runs is
never found again, so it is never built from a temporary name, a process
id or the time.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this module
    sets no other directory.
  * Otherwise: ``<checkout>/.cache/jax``, which git ignores.

Called first thing by ``chip_smoke.py``, the ``repro.launch`` simulator and
LM CLIs, and ``benchmarks/run.py``.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    # most of a run's compile time is hundreds of sub-second programs
    # (per-stage glue, the references), which JAX's default threshold of
    # one second never caches.  Caching them all costs a cold start: on a
    # TPU v5e each write took about 0.34 s, and chip_smoke.py's cold run
    # rose from 96 s to 347 s while its warm run fell to 29 s.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(CHECKOUT, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
