"""Persistent artifact cache: pay the cold path once per dataset.

The framework's dominant fixed costs are both *derivable* artifacts:

- **GRR plan ETL** — the compiled gather-route-reduce plan
  (``data.grr``) is a pure function of (cols, vals, dim) × the plan
  configuration: minutes of re-derivation per run at the bench shape
  for bytes that never change between runs.
- **XLA compilation** — identical across runs for identical program
  shapes, and a large part of a cold run.

Snap ML's 10×-over-Spark wins come largely from keeping data and
derived structures resident across iterations (PAPERS.md); this package
applies the same argument across *runs*: the second run of any workload
loads its plan from disk (``plan_cache``) and replays compiled XLA
programs from JAX's persistent compilation cache (``compile_cache``)
instead of re-deriving either.

The plan cache's layout on disk (one directory, safe to delete
wholesale; the XLA cache's place is ``compile_cache``'s decision
alone)::

    <cache_dir>/
      plans/grr-<fp16>-<cfg12>-v<F>.<P>.npz   # serialized plans

Keying (see ``plan_cache``): ``fp16`` is a content hash of the exact
ELL arrays + table width, ``cfg12`` hashes the plan-affecting build
options, ``F``/``P`` are the serialization-format and planner/builder
versions — any change to planner semantics bumps
``data.grr.PLANNER_VERSION`` and orphans old entries (they are
harmlessly ignored).  Corrupt or truncated files fall back to a fresh
build (tested).
"""

from photon_ml_tpu.cache.compile_cache import (
    cache_entry_count,
    enable_compilation_cache,
)
from photon_ml_tpu.cache.plan_cache import (
    atomic_savez,
    dataset_fingerprint,
    load_plan,
    plan_cache_path,
    plan_config_key,
    save_plan,
)

__all__ = [
    "atomic_savez",
    "cache_entry_count",
    "dataset_fingerprint",
    "enable_compilation_cache",
    "load_plan",
    "plan_cache_path",
    "plan_config_key",
    "save_plan",
]
