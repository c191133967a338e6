"""Shared skip-guard for tests that need REAL multi-process CPU
collectives (``jax.distributed`` + cross-process psum).

Some jaxlib CPU backends cannot run multiprocess computations at all —
a worker that tries dies with the error text pinned as
``fleet.MULTIPROC_UNSUPPORTED_MARKER``.  These guards share the fleet
module's single cached capability probe instead of per-test ad-hoc
marker scans, so every multi-process test skips (or runs) on the same
verdict a launcher's transport selection uses:

- ``require_multiprocess_collectives()`` — probe up front (one cached
  2-worker probe per test process) and ``pytest.skip`` when
  unsupported; for tests whose own workers are expensive enough that
  learning the answer first is cheaper.
- ``skip_if_multiprocess_wall(outs)`` — post-hoc: for tests whose own
  workers double as the probe, skip when any worker's output hit the
  backend's multiprocess wall.
"""

from __future__ import annotations

from collections.abc import Iterable

import pytest

from photon_ml_tpu.parallel import fleet

SKIP_REASON = ("this jaxlib's CPU backend has no multiprocess "
               "collectives; needs a newer jaxlib or real devices")


def require_multiprocess_collectives() -> None:
    """Skip the calling test unless this box can run real 2-process
    CPU collectives."""
    if not fleet.probe_cpu_multiprocess_collectives():
        pytest.skip(SKIP_REASON)


def skip_if_multiprocess_wall(outs: Iterable[str | None]) -> None:
    """Skip the calling test when any worker output shows the CPU
    backend's multiprocess wall."""
    if any(fleet.MULTIPROC_UNSUPPORTED_MARKER in (o or "")
           for o in outs):
        pytest.skip(SKIP_REASON)
