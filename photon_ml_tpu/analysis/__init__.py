"""photon-lint: static AST invariant checkers + runtime guard harness.

Rounds 6-10 earned their speedups by hand-enforcing invariants --
module-level jitted programs so sequential grid points stop recompiling
(PR 2), prefetch backpressure and store reader accounting so the async
pipeline cannot un-bound what the LRU window bounds (PR 3), host
float64 metric folds (PR 4) -- but nothing in the repo *checked* any of
it, so the multi-host streaming, fused-CD, and serving tiers queued in
ROADMAP items 1-3 (more threads, more compiles, more host<->device
traffic) could silently regress them.  "Understanding and Optimizing
the Performance of Distributed ML Applications on Apache Spark"
(PAPERS.md) documents exactly this failure mode at the reference
system's scale: the dominant costs were accidental serialization /
recompute patterns invisible until profiled.  This package encodes our
contracts twice:

- ``checkers``: AST-based static rules over the whole package
  (jit discipline, tracer hygiene, thread/lock discipline, accumulator
  dtype, env hygiene, slow-test markers), run by
  ``python -m photon_ml_tpu.analysis`` and enforced in tier-1 by
  ``tests/test_analysis.py::test_repo_clean``.
- ``guards``: runtime context managers (compile counting via
  ``jax.log_compiles``, ``jax.check_tracer_leaks``,
  ``jax.transfer_guard``) with budget assertions wired into the
  hot-path tests.
"""

from photon_ml_tpu.analysis.checkers import (  # noqa: F401
    RULES,
    Violation,
    check_source,
    run_checks,
)
from photon_ml_tpu.analysis.guards import (  # noqa: F401
    count_compiles,
    no_implicit_transfers,
    tracer_leak_guard,
)
