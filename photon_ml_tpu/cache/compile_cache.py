"""JAX persistent compilation cache wiring.

Compiling the solver and scoring programs is a large part of a cold
run, and it is re-paid on every run for identical program shapes.
JAX's persistent compilation cache keys compiled executables by (HLO,
compile options, backend, *cache directory path*), so a run only hits
what an earlier run wrote when both name the same directory.

``enable_compilation_cache`` is the one place that decides where that
directory is; the drivers, the estimator, the server and ``chip_smoke.py``
all call it and choose nothing themselves:

- where JAX already has a cache directory — it reads
  ``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir`` at
  import — this module sets none;
- where it has none, the cache lives at ``DEFAULT_CACHE_DIR``: one
  fixed, git-ignored directory at the root of the checkout, derived
  from this package's own location — never from a temp name, a pid, a
  uid or the time, any of which would move the path and so never hit.

An unwritable cache directory fails the run here, at the call, rather
than silently compiling cold for ever.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the
    directory in effect (see the module docstring for which).

    The min-compile-time floor is dropped to 0.5 s so the solver and
    scoring programs (seconds to minutes of XLA time each) all persist
    without caching the dispatch-layer trivia.  Every driver's first
    act, so the compile path's ledger starts listening here: what
    compiles before the first stage is charged to its (0, "") row.
    Idempotent."""
    import jax

    from photon_ml_tpu import telemetry

    telemetry.listen_to_compiles()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = jax.config.jax_compilation_cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK):
        raise PermissionError(
            f"persistent compilation cache {cache_dir!r} is not writable")
    logger.info("persistent compilation cache at %s", cache_dir)
    return cache_dir


def cache_entry_count(cache_dir: str) -> int:
    """Number of compiled programs in ``cache_dir`` (0 if absent)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for name in os.listdir(cache_dir)
               if name.endswith("-cache"))
