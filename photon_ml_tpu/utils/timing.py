"""Device timing helpers for the benchmarks.

JAX dispatch is asynchronous: a call returns before the device has
finished, so a timing must end in ``jax.block_until_ready`` or it
measures the enqueue (on the v5e an 8192³ bf16 matmul enqueues in
0.19 ms and takes 6.6 ms behind the fence, 167 TFLOP/s of the chip's
197 — PERF.md, PR 22).  ``measure`` times ``iters`` back-to-back
dispatches and fences once at the end: a device's queue executes
programs in FIFO order, so (total / iters) is the per-call device time
once the queue is deeper than the dispatch latency.  Per-call dispatch
overhead makes single-call timings meaningless for millisecond-scale
kernels — measure loops, or wrap the iteration in ``lax.scan`` (see
``measure_scanned``).
"""

from __future__ import annotations

import time
from typing import Callable

import jax


def measure(fn: Callable, *args, iters: int = 20, warmup: int = 1) -> float:
    """Median-free queue-drain timing: seconds per call.

    Dispatches ``iters`` calls back to back and fences once; the queue
    serializes execution, so dispatch overhead overlaps device work.
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def measure_scanned(fn: Callable, *args, length: int = 10,
                    iters: int = 3) -> float:
    """Seconds per call with the loop inside one jitted ``lax.scan``.

    Removes per-dispatch overhead entirely; ``fn``'s first argument is
    treated as the loop carry (its output must match its shape/dtype).
    """
    import jax.numpy as jnp  # noqa: F401  (kept local: utils stays light)

    def chain(carry, *rest):
        def body(c, _):
            return fn(c, *rest), None
        out, _ = jax.lax.scan(body, carry, None, length=length)
        return out

    # photon-lint: disable=jit-in-function (measurement harness, by design)
    chained = jax.jit(chain)
    return measure(chained, *args, iters=iters) / length
