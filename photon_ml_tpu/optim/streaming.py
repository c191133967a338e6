"""Streaming (chunk-accumulated) objective + host-driven L-BFGS/OWL-QN.

Reference counterpart: the per-iteration Spark round —
``broadcast(w) → per-partition aggregator fold → treeAggregate`` —
whose partitions never co-reside in memory (SURVEY.md §2.2, §5.8
[expected structure, mount unavailable]).  Here the "partitions" are
the congruent device-program chunks of ``data.chunked_batch``: each
objective evaluation replays ONE compiled per-chunk program K times,
double-buffering the host→device transfer of chunk i+1 under chunk i's
compute, and accumulates (value, gradient, HVP, Hessian-diagonal)
partials on device.  Exact: every data-side quantity is a linear
reduction over examples; regularization and the Gaussian prior are
example-independent and added once, outside the chunk loop.

The resident solvers (``optim.lbfgs`` / ``optim.tron``) run their whole
optimize loop as one device program — impossible when each objective
evaluation needs host-side chunk swaps.  ``streaming_lbfgs_solve`` is
the host-driven mirror of ``lbfgs_solve``: the same two-loop recursion,
Armijo backtracking (with the OWL-QN orthant projection and
pseudo-gradient), curvature-guarded (s, y) updates, and convergence
tests, but with a Python outer loop calling a host-level
``value_and_grad``.  Per-iteration [dim]-vector math dispatches eagerly
(a handful of cached device ops — microseconds of compute); the data
passes dominate, exactly as in the reference's driver loop.

λ-sweep amortization: the data passes are also λ-INDEPENDENT (reg is
added outside the chunk loop), so ``value_and_gradient_swept`` feeds L
stacked coefficient lanes from ONE double-buffered chunk sweep and
``streaming_lbfgs_solve_swept`` runs the whole regularization grid as
one masked-lane solve — data passes per solver iteration drop from L
to ~1 (see ``ops.objective`` swept surface).
"""

from __future__ import annotations

import hashlib
import logging
import queue
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.reliability import checkpoint as _ckpt
from photon_ml_tpu.reliability import faults as _faults
from photon_ml_tpu.telemetry import convergence as _conv
from photon_ml_tpu.telemetry import device as _device
from photon_ml_tpu.telemetry import monitor as _mon
from photon_ml_tpu.data.chunked_batch import ChunkedBatch
from photon_ml_tpu.ops.objective import (
    GLMObjective,
    sweep_value,
    sweep_value_and_gradient,
)
from photon_ml_tpu.ops.regularization import (
    RegularizationContext,
    SweptRegularization,
)
from photon_ml_tpu.optim.base import (
    OptimizationResult,
    OptimizerConfig,
    StatesTracker,
    grad_converged,
    loss_converged,
)
from photon_ml_tpu.optim.lbfgs import _pseudo_gradient
from photon_ml_tpu.optim.tron import (
    _DELTA_MIN,
    _ETA0,
    _SIGMA1,
    _SIGMA3,
    _boundary_tau,
)

logger = logging.getLogger(__name__)

Array = jax.Array

_CURVATURE_EPS = 1e-10

# Consumer-side stall deadline (seconds) for the prefetch pipeline: a
# wedged disk (or a producer thread killed without a sentinel) turns
# into ONE actionable error after this long, never an eternal
# ``q.get`` (ISSUE 9).  Generous by design — a healthy chunk read is
# milliseconds, so ten minutes means the disk tier is truly gone.
DEFAULT_STALL_TIMEOUT_S = 600.0


def _fleet_reducer():
    """The active fleet's per-chunk allreduce, or None (single host).
    Lazy import: ``parallel`` pulls mesh machinery this module only
    needs when a mesh (or fleet) is actually in play."""
    from photon_ml_tpu.parallel import fleet

    return fleet.reducer()


def _place_chunk(chunk, mesh):
    """Host chunk → device: plain device_put, or example-sharded
    assembly of the per-device sub-batches onto the mesh."""
    if mesh is None:
        return jax.device_put(chunk)
    from jax.sharding import NamedSharding

    from photon_ml_tpu.parallel.mesh import batch_spec

    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, batch_spec())

    def asm(*leaves):
        placed = [jax.device_put(lf, d) for lf, d in zip(leaves, devices)]
        gshape = ((len(devices) * leaves[0].shape[0],)
                  + tuple(leaves[0].shape[1:]))
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, placed)

    return jax.tree.map(asm, *chunk)


class ChunkPrefetcher:
    """Background disk → host → device pipeline stage.

    One thread walks the sweep's chunk order ahead of the consumer:
    ``load(i)`` pulls the host pieces (the chunk store's disk read /
    LRU window), ``place`` starts the ASYNC host→device transfer, and
    the (host, device) pair lands in a bounded queue of depth
    ``depth`` — so chunk i's device compute overlaps chunk
    i+1..i+depth's disk reads AND transfers, the third pipeline level
    in front of the classic device double-buffer.  The host reference
    rides in the queue item until the consumer takes it, so the LRU
    window can never free arrays out from under an in-flight copy.

    Generic over the chunk source since ISSUE 4 (``load``/``place``
    callables + optional ``store`` for reader accounting): the training
    objective feeds it ``ChunkedBatch.chunk`` + the mesh-aware
    placement, the streaming scorer its score-chunk store reader +
    plain ``device_put``.

    Determinism: the queue preserves the thread's (sweep) order and
    ``next(expect)`` asserts it — the chunk visit order the parity and
    ``sweeps``-odometer contracts rely on cannot be reordered by the
    pipeline.  The thread registers as a store reader so
    ``ChunkStore.assert_quiesced`` can prove no use-after-evict.
    """

    _SENTINEL = object()

    def __init__(self, load, place, depth: int, store=None,
                 stall_timeout_s: float | None = None):
        self._load = load
        self._place = place
        self._store = store
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.stall_timeout_s = (DEFAULT_STALL_TIMEOUT_S
                                if stall_timeout_s is None
                                else float(stall_timeout_s))

    def start(self, order) -> None:
        if self._store is not None:
            self._store.begin_read()
        self._thread = threading.Thread(
            target=self._run, args=(list(order),), daemon=True,
            name="photon-chunk-prefetch")
        self._thread.start()

    def _put(self, item) -> bool:
        t = telemetry.active()
        if t is None:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.05)
                    return True
                except queue.Full:  # photon-lint: disable=swallowed-exception (bounded poll; the loop re-checks the stop flag each lap)
                    continue
            return False
        # Telemetry-on path: account full-queue stall time (a full
        # queue means the producer is AHEAD — informational, not a
        # problem) and emit liveness heartbeats while blocked, so a
        # hung consumer shows as a stalled-but-alive producer.
        start = time.perf_counter()
        beat = start
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                stalled = time.perf_counter() - start
                if stalled > 0.01:   # an actual full-queue wait
                    t.count("prefetch.producer_stall_s", stalled)
                return True
            except queue.Full:
                now = time.perf_counter()
                if now - beat >= t.heartbeat_s:
                    t.heartbeat("prefetch-producer", state="queue_full",
                                stalled_s=round(now - start, 3))
                    beat = now
        return False

    def _run(self, order) -> None:
        t = telemetry.active()
        last_beat = time.perf_counter()
        try:
            for i in order:
                if self._stop.is_set():
                    return
                with telemetry.span("prefetch_load", cat="prefetch",
                                    chunk=i):
                    _faults.fire("prefetch.load", chunk=i)
                    host = self._load(i)             # disk -> host
                with telemetry.span("prefetch_place", cat="prefetch",
                                    chunk=i):
                    _faults.fire("prefetch.place", chunk=i)
                    buf = self._place(host)          # host -> device
                if t is not None:
                    t.count("prefetch.chunks_produced")
                    t.gauge("prefetch.queue_depth", self._q.qsize())
                    now = time.perf_counter()
                    if now - last_beat >= t.heartbeat_s:
                        t.heartbeat("prefetch-producer", chunk=i)
                        last_beat = now
                if not self._put((i, host, buf)):
                    return
        except BaseException as e:
            # Death event FIRST (hung-run forensics: the JSONL shows
            # which stage died even if the consumer never drains the
            # sentinel), then the error RIDES THE QUEUE to the
            # consumer: an attribute would be an unlocked cross-thread
            # write (photon-lint unlocked-shared-write); the queue's
            # internal lock gives the happens-before edge for free.
            telemetry.thread_exception("prefetch-producer", e)
            logger.warning("chunk prefetch thread died: %r", e)
            self._put((self._SENTINEL, e, None))
        finally:
            if self._store is not None:
                self._store.end_read()

    def next(self, expect: int):
        """The next placed chunk; raises the producer's error, and
        asserts the deterministic order.  The wait is a BOUNDED poll,
        never an eternal ``q.get`` (ISSUE 9): a producer thread that
        died without delivering (killed, lost without a sentinel)
        raises one actionable error immediately, and a wedged disk
        read trips ``stall_timeout_s`` into an actionable timeout.
        With telemetry active the blocking wait is accounted
        (``prefetch.consumer_wait_s`` — the numerator of the
        overlap-efficiency derivation) and heartbeats flow while
        starved, so a hung producer shows as a waiting-but-alive
        consumer."""
        t = telemetry.active()
        start = time.perf_counter()
        beat = start
        while True:
            try:
                i, host, buf = self._q.get(timeout=0.05)
                break
            except queue.Empty:
                now = time.perf_counter()
                thread = self._thread
                if ((thread is None or not thread.is_alive())
                        and self._q.empty()):
                    telemetry.count("reliability.actionable_errors")
                    raise RuntimeError(
                        f"prefetch producer died without delivering "
                        f"chunk {expect} (thread gone, queue empty, no "
                        "in-band error); see the run log's "
                        "thread_exception / heartbeat events for the "
                        "stage that stopped")
                if now - start > self.stall_timeout_s:
                    telemetry.count("prefetch.stall_timeouts")
                    telemetry.count("reliability.actionable_errors")
                    raise TimeoutError(
                        f"prefetch pipeline stalled {now - start:.1f}s "
                        f"waiting for chunk {expect} (stall_timeout_s="
                        f"{self.stall_timeout_s:g}): the disk/staging "
                        "tier is wedged — check spill-dir health; the "
                        "producer thread is still alive, so its "
                        "heartbeat events name the stuck stage")
                if t is not None and now - beat >= t.heartbeat_s:
                    t.heartbeat("prefetch-consumer",
                                state="queue_empty", expect=expect,
                                waiting_s=round(now - start, 3))
                    beat = now
        if t is not None:
            t.count("prefetch.consumer_wait_s",
                    time.perf_counter() - start)
            t.count("prefetch.chunks_consumed")
        if i is self._SENTINEL:
            raise host   # the producer's exception, delivered in-band
        if i != expect:
            raise AssertionError(
                f"prefetch order violated: got chunk {i}, "
                f"expected {expect}")
        del host   # consumer now owns the device buffer
        return buf

    def close(self, join_timeout_s: float = 10.0) -> None:
        """Quiesce: stop the producer, drain, join — with a DEADLINE.
        A producer wedged inside a blocking ``load`` (hung disk/NFS)
        cannot observe the stop flag, and close() runs while the stall
        TimeoutError unwinds — an unbounded join would re-hang the run
        the deadline just turned into an error (review finding).  The
        thread is a daemon, so abandoning it is safe.  Idempotent."""
        t = self._thread
        if t is None:
            return
        self._stop.set()
        deadline = time.monotonic() + join_timeout_s
        while t.is_alive() and time.monotonic() < deadline:
            try:
                self._q.get_nowait()   # unblock a full-queue producer
            except queue.Empty:
                t.join(timeout=0.05)
        if t.is_alive():
            logger.warning(
                "prefetch thread did not exit within %.1fs (blocked "
                "in a chunk load?); abandoning daemon thread",
                join_timeout_s)
            telemetry.count("prefetch.abandoned_threads")
        self._thread = None


# Historical name (round 8); the class went public when the streaming
# scorer started reusing it.
_ChunkPrefetcher = ChunkPrefetcher


def prefetch_stream(load, place, order, depth: int, store=None):
    """Yield ``(i, placed)`` for every ``i`` in ``order`` through the
    three-tier prefetch pipeline (disk read → host staging → async
    device_put, ``depth`` chunks ahead), or synchronously when
    ``depth <= 0`` — the one entry point for consumers that drive a
    chunk sweep themselves instead of owning a ``ChunkedGLMObjective``
    (the streamed random-effect coordinate's per-bucket solves, ISSUE
    5).  The prefetcher is always closed (and the store reader
    released) when the generator exits, including on error or early
    ``break`` — quiescence is structural, not a caller obligation."""
    order = list(order)
    if depth <= 0:
        if store is not None:
            store.begin_read()
        try:
            for i in order:
                yield i, place(load(i))
        finally:
            if store is not None:
                store.end_read()
        return
    pf = ChunkPrefetcher(load, place, depth, store=store)
    pf.start(order)
    try:
        for i in order:
            yield i, pf.next(i)
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# Per-chunk device programs, jitted at MODULE level so every
# ChunkedGLMObjective instance shares one compile cache: λ-grid /
# tuning points build a fresh objective per point, and per-instance jit
# wrappers would recompile the identical program once per point (the
# objective rides as a pytree ARGUMENT — its reg/norm arrays, λ
# included, are traced leaves, never HLO constants).
# ---------------------------------------------------------------------------

_jit_vg = jax.jit(lambda o, w, b: o.value_and_gradient(w, b))
_jit_val = jax.jit(lambda o, w, b: o.value(w, b))
_jit_hvp = jax.jit(lambda o, w, v, b: o.hessian_vector(w, v, b))
_jit_hd = jax.jit(lambda o, w, b: o.hessian_diagonal(w, b))
_jit_margins = jax.jit(lambda o, w, b: o.predict_margins(w, b))
_jit_xdot_obj = jax.jit(lambda o, w, b: o.x_dot(w, b))
_jit_xdot = jax.jit(lambda w, b: b.x_dot(w))


@partial(jax.jit, static_argnums=(3,))
def _jit_vg_swept(o, W, b, lane_map):
    return sweep_value_and_gradient(o, W, b, use_map=lane_map)


@partial(jax.jit, static_argnums=(3,))
def _jit_val_swept(o, W, b, lane_map):
    return sweep_value(o, W, b, use_map=lane_map)


@jax.jit
def _swept_direction(PG, W, S_buf, Y_buf, Rho, head, count, l1):
    """Per-lane two-loop recursion + safeguards as ONE device program
    (the host-driven swept solver dispatches this once per iteration;
    eagerly it would be ~2·m·L fancy-indexed ops per step).

    Returns (D [L, d], Xi [L, d] | None): the per-lane descent
    directions and, when ``l1`` is given (OWL-QN), the search orthants.
    """
    m, L, d = S_buf.shape
    lanes = jnp.arange(L)
    q = PG
    alphas = []
    for j in range(m):
        idx = (head - 1 - j) % m
        valid = j < count
        s_j, y_j = S_buf[idx, lanes], Y_buf[idx, lanes]
        a = Rho[idx, lanes] * jnp.sum(s_j * q, axis=-1)
        a = jnp.where(valid, a, 0.0)
        q = q - a[:, None] * y_j
        alphas.append((a, idx, valid))
    newest = (head - 1) % m
    y_new = Y_buf[newest, lanes]
    gamma = jnp.where(
        count > 0,
        1.0 / jnp.maximum(
            Rho[newest, lanes] * jnp.sum(y_new * y_new, axis=-1),
            _CURVATURE_EPS),
        1.0,
    )
    r = gamma[:, None] * q
    for a, idx, valid in reversed(alphas):
        s_j, y_j = S_buf[idx, lanes], Y_buf[idx, lanes]
        beta = Rho[idx, lanes] * jnp.sum(y_j * r, axis=-1)
        upd = s_j * (a - beta)[:, None]
        r = r + jnp.where(valid[:, None], upd, 0.0)
    D = -r
    Xi = None
    if l1 is not None:
        D = jnp.where(D * -PG > 0.0, D, 0.0)
        Xi = jnp.where(W != 0.0, jnp.sign(W), jnp.sign(-PG))
    bad = jnp.sum(PG * D, axis=-1) >= 0.0
    D = jnp.where(bad[:, None], -PG, D)
    return D, Xi


@jax.jit
def _swept_push(S_buf, Y_buf, Rho, head, count, s, y, good):
    """Masked per-lane circular-buffer push of curvature pairs — one
    device program per iteration."""
    L = head.shape[0]
    lanes = jnp.arange(L)
    sy = jnp.sum(s * y, axis=-1)
    S_buf = S_buf.at[head, lanes].set(
        jnp.where(good[:, None], s, S_buf[head, lanes]))
    Y_buf = Y_buf.at[head, lanes].set(
        jnp.where(good[:, None], y, Y_buf[head, lanes]))
    Rho = Rho.at[head, lanes].set(
        jnp.where(good, 1.0 / jnp.maximum(sy, _CURVATURE_EPS),
                  Rho[head, lanes]))
    m = S_buf.shape[0]
    head = jnp.where(good, (head + 1) % m, head)
    count = jnp.where(good, jnp.minimum(count + 1, m), count)
    return S_buf, Y_buf, Rho, head, count


class ChunkedGLMObjective:
    """``GLMObjective`` surface over a ``ChunkedBatch``.

    Methods take only ``w`` (the batch is owned): the streaming solver
    cannot donate or close over a resident batch, so the usual
    ``(w, batch)`` calling convention has no meaning here.

    ``max_resident`` chunks stay live on device across evaluations
    (datasets that fit entirely set it ≥ n_chunks and pay the transfer
    once — the resident and streaming regimes are one code path);
    beyond it, chunks are re-placed each pass, double-buffered.

    When the batch carries a spill store (``data.chunk_store`` — the
    disk tier), each sweep runs a background ``_ChunkPrefetcher``
    instead: disk read → host staging → async device_put of chunks
    i+1..i+``prefetch_depth`` overlap chunk i's device compute, and the
    chunk visit order (hence float-summation order and the ``sweeps``
    odometer) is exactly the resident path's.

    ``sweeps`` counts full chunk sweeps since construction — the
    data-pass odometer that shows the L → 1 passes-per-iteration
    amortization of a swept solve.
    """

    def __init__(self, objective: GLMObjective, batch: ChunkedBatch,
                 max_resident: int = 1, prefetch_depth: int = 2):
        self.objective = objective
        self.batch = batch
        self.max_resident = max_resident
        self.prefetch_depth = prefetch_depth
        self.sweeps = 0
        self._cache: dict = {}
        self._active_prefetcher: _ChunkPrefetcher | None = None
        inner = objective.replace(
            reg=RegularizationContext.none(), prior=None)
        self._mesh = batch.mesh
        if self._mesh is not None:
            from photon_ml_tpu.parallel import DistributedGLMObjective

            self._inner = DistributedGLMObjective(
                objective=inner, mesh=self._mesh)
        else:
            self._inner = inner
        # Swept evaluations lane-loop (lax.map) instead of vmapping when
        # the per-chunk program has no batching rule: GRR chunk plans
        # (Pallas kernel) and shard_mapped mesh objectives.  The chunk
        # still streams ONCE either way — the amortization is the
        # transfer, not the read.
        self._lane_map = batch.layout == "grr" or self._mesh is not None

    # -- chunk residency ---------------------------------------------------

    def invalidate(self) -> None:
        """Drop device copies (after ``ChunkedBatch.set_offsets``).

        The prefetch pipeline is quiesced FIRST, and the store must
        prove it (``assert_quiesced``): freeing buffers while the
        background thread is mid device_put on an LRU-windowed chunk
        would be a use-after-evict race."""
        pf = self._active_prefetcher
        if pf is not None:
            pf.close()
            self._active_prefetcher = None
        if self.batch.store is not None:
            self.batch.store.assert_quiesced()
        self._cache.clear()

    def capture_device_cost(self, w: Array) -> None:
        """Explicit device-cost capture of the per-chunk value+gradient
        program against chunk 0 (ISSUE 8).  A caller that times sweeps calls this
        right after warmup so the capture's AOT relower lands OUTSIDE the
        timed sweeps; the in-sweep capture then finds the name already
        resolved.  2-D ``w`` captures the swept program.  No-op without
        an active telemetry session or with an empty batch."""
        if telemetry.active() is None or self.batch.n_chunks == 0:
            return
        owned = self.batch.owned_chunk_ids
        if not owned:   # all-sentinel fleet host: nothing to capture
            return
        store = self.batch.store
        if store is not None:
            store.begin_read()
        try:
            b = _place_chunk(self.batch.chunk(owned[0]), self._mesh)
        finally:
            if store is not None:
                store.end_read()
        w = jnp.asarray(w, jnp.float32)
        if w.ndim == 2:
            _device.maybe_capture(
                "chunk_vg_swept", _jit_vg_swept,
                (self._inner, w, b, self._lane_map),
                span="chunk_compute")
        else:
            _device.maybe_capture("chunk_vg", _jit_vg,
                                  (self._inner, w, b),
                                  span="chunk_compute")

    def _get(self, i: int):
        if i in self._cache:
            return self._cache[i]
        b = _place_chunk(self.batch.chunk(i), self._mesh)
        if len(self._cache) < self.max_resident:
            self._cache[i] = b
        return b

    def _chunk_stream(self):
        """Device chunks in deterministic order 0..K-1, pipelined.

        Spill-store batches run the three-tier prefetch thread (disk →
        host window → async device_put, ``prefetch_depth`` deep);
        resident batches keep the classic device double-buffer (the
        transfer of chunk i+1 dispatches before chunk i's compute).

        Yields ``(chunk_id, device_chunk)`` in this host's schedule
        order.  Fleet hosts visit only their shard; sentinel steps
        (``fleet.EMPTY_CHUNK`` — ragged-shard padding so every host
        takes the same number of chunk barriers) yield
        ``(EMPTY_CHUNK, None)`` and stream nothing."""
        sched = self.batch.chunk_schedule
        real = [i for i in sched if i >= 0]
        if not sched:
            return
        if self.batch.store is not None and self.prefetch_depth > 0 \
                and real:
            pf = ChunkPrefetcher(
                self.batch.chunk,
                lambda host: _place_chunk(host, self._mesh),
                self.prefetch_depth, store=self.batch.store)
            self._active_prefetcher = pf
            pf.start(real)
            try:
                for i in sched:
                    yield (i, pf.next(i)) if i >= 0 else (i, None)
            finally:
                pf.close()
                self._active_prefetcher = None
            return
        nxt = self._get(real[0]) if real else None
        pos = 0
        for i in sched:
            if i < 0:
                yield i, None
                continue
            cur = nxt
            pos += 1
            if pos < len(real):
                nxt = self._get(real[pos])  # async transfer under compute
            yield i, cur

    def _sweep(self, per_chunk, combine, cost=None, zero=None):
        """Stream this host's chunk schedule through ``per_chunk``,
        pipelined.

        Out-of-core batches add BACKPRESSURE: chunk i-1's accumulate is
        fenced before chunk i dispatches, so the async dispatch queue
        holds one chunk's buffers + temporaries instead of all K —
        without it a K-chunk pass keeps every placed chunk live until
        its compute retires, un-bounding exactly the memory the store
        exists to bound.  On a device backend the chunk programs
        serialize on the accelerator anyway (the prefetch thread keeps
        transfers ahead regardless), so the fence costs a dispatch
        bubble, not overlap.

        ``cost``: optional ``(name, jit_fn, chunk → args)`` device-cost
        capture spec (ISSUE 8) — resolved once per session per name on
        the FIRST chunk, right after its dispatch (the lowering cache is
        then warm, so the capture relowers without a new compile
        record).

        ``zero``: the sentinel partial (``() → same pytree shape as
        ``per_chunk``'s result, all zeros``).  Fleet runs REQUIRE it —
        a host's sentinel steps and all-sentinel hosts contribute exact
        zeros to the per-chunk fleet reduction, so ragged shards never
        skew the barrier count.  Outside a fleet it is never called.

        Fleet runs reduce each chunk partial across hosts (the
        chunk-synchronized barrier) and every host accumulates the
        SAME global totals — solver state stays replicated, so the
        solvers above this line are fleet-oblivious."""
        self.sweeps += 1
        telemetry.count("solver.sweeps")
        fred = _fleet_reducer()
        if fred is not None and zero is None:
            raise ValueError(
                "fleet sweep needs a zero() sentinel template")
        bounded = self.batch.store is not None
        # Per-program dispatch times are only MEANINGFUL on the bounded
        # (spilled) path, where the backpressure fence makes each
        # iteration's wall time cover a chunk's device compute; the
        # resident path dispatches asynchronously (tens of µs observed
        # regardless of program cost), which would make the report's
        # roofline fractions nonsense.
        timed = (cost is not None and bounded
                 and telemetry.active() is not None)
        acc = None
        steps = len(self.batch.chunk_schedule)
        with telemetry.span("sweep", cat="solver",
                            chunks=self.batch.n_chunks):
            for ci, (cid, cur) in enumerate(self._chunk_stream()):
                # The span covers the backpressure fence too: that wait
                # IS the previous chunk's device compute retiring.
                t0 = time.perf_counter() if timed else None
                with telemetry.span("chunk_compute", cat="device"):
                    if bounded and acc is not None:
                        jax.block_until_ready(acc)
                    out = per_chunk(cur) if cid >= 0 else zero()
                # Live chunk progress (ISSUE 10): the monitor derives
                # rolling chunk throughput + a within-sweep ETA; a
                # no-op global read when monitoring is off, throttled
                # to its wall-clock cadence when on.
                _mon.progress("train.sweep", ci + 1, steps,
                              unit="chunks")
                newly_captured = False
                if acc is None and cost is not None and cid >= 0:
                    name, fn, mk_args = cost
                    newly_captured = _device.maybe_capture(
                        name, fn, mk_args(cur), span="chunk_compute")
                if fred is not None:
                    # Chunk barrier: this step's partial summed across
                    # the fleet (each host contributed a DIFFERENT
                    # chunk, or zeros past its ragged shard).
                    out = fred.reduce(out)
                    if cid >= 0:
                        telemetry.count("fleet.chunks_streamed")
                if timed and not newly_captured and cid >= 0:
                    # Per-PROGRAM dispatch histogram: the shared
                    # "chunk_compute" span pools every chunk program's
                    # dispatches, so the device report joins each
                    # captured cost against this name-keyed measure
                    # instead (review finding: a pooled mean overstates
                    # the expensive program and understates the cheap
                    # one whenever a solve runs both).  The capture
                    # chunk — this program's first dispatch, which pays
                    # the XLA compile — is excluded from the measure.
                    telemetry.observe("device.dispatch_s." + cost[0],
                                      time.perf_counter() - t0)
                acc = out if acc is None else combine(acc, out)
        return acc

    # -- TwiceDiffFunction surface (batch owned) ---------------------------

    def value(self, w: Array) -> Array:
        w = jnp.asarray(w, jnp.float32)
        val = self._sweep(lambda b: _jit_val(self._inner, w, b),
                          lambda a, x: a + x,
                          cost=("chunk_value", _jit_val,
                                lambda b: (self._inner, w, b)),
                          zero=lambda: jnp.zeros((), jnp.float32))
        val = val + self.objective.reg.l2_value(w)
        if self.objective.prior is not None:
            val = val + self.objective.prior.value(w)
        return val

    def value_and_gradient(self, w: Array) -> tuple[Array, Array]:
        w = jnp.asarray(w, jnp.float32)
        f, g = self._sweep(
            lambda b: _jit_vg(self._inner, w, b),
            lambda a, x: (a[0] + x[0], a[1] + x[1]),
            cost=("chunk_vg", _jit_vg, lambda b: (self._inner, w, b)),
            zero=lambda: (jnp.zeros((), jnp.float32),
                          jnp.zeros_like(w)))
        reg = self.objective.reg
        f = f + reg.l2_value(w)
        g = g + reg.l2_gradient(w)
        if self.objective.prior is not None:
            f = f + self.objective.prior.value(w)
            g = g + self.objective.prior.gradient(w)
        return f, g

    def gradient(self, w: Array) -> Array:
        return self.value_and_gradient(w)[1]

    def hessian_vector(self, w: Array, v: Array) -> Array:
        w = jnp.asarray(w, jnp.float32)
        v = jnp.asarray(v, jnp.float32)
        # Auxiliary pass (not a line-search evaluation): the report's
        # sweep-odometer reconciliation accounts it separately.
        telemetry.count("solver.aux_sweeps")
        hv = self._sweep(lambda b: _jit_hvp(self._inner, w, v, b),
                         lambda a, x: a + x,
                         zero=lambda: jnp.zeros_like(w))
        hv = hv + self.objective.reg.l2_hessian_vector(v)
        if self.objective.prior is not None:
            hv = hv + self.objective.prior.hessian_vector(v)
        return hv

    def hessian_diagonal(self, w: Array) -> Array:
        w = jnp.asarray(w, jnp.float32)
        telemetry.count("solver.aux_sweeps")
        hd = self._sweep(lambda b: _jit_hd(self._inner, w, b),
                         lambda a, x: a + x,
                         zero=lambda: jnp.zeros_like(w))
        hd = hd + self.objective.reg.l2_hessian_diagonal(w)
        if self.objective.prior is not None:
            hd = hd + self.objective.prior.hessian_diagonal()
        return hd

    def hvp_pass(self, w: Array, v: Array) -> Array:
        """One chunk-accumulated H(w)·v data pass for Steihaug CG
        (ISSUE 17).

        Same math as ``hessian_vector`` — each chunk's J^T D J v
        partial is one module-jitted device program, fleet psum-reduced
        per chunk, with the L2/prior curvature added ONCE outside the
        chunk loop (example-independent, so the pass stays exact) — but
        accounted under ``solver.hvp_sweeps``: CG inner-loop passes are
        the quantity the TRON-vs-L-BFGS comparison is ABOUT, so the
        sweep odometer attributes them to their own bucket instead of
        folding them into ``aux_sweeps`` (variance/diagnostic passes).
        """
        w = jnp.asarray(w, jnp.float32)
        v = jnp.asarray(v, jnp.float32)
        telemetry.count("solver.hvp_sweeps")
        hv = self._sweep(lambda b: _jit_hvp(self._inner, w, v, b),
                         lambda a, x: a + x,
                         cost=("chunk_hvp", _jit_hvp,
                               lambda b: (self._inner, w, v, b)),
                         zero=lambda: jnp.zeros_like(w))
        hv = hv + self.objective.reg.l2_hessian_vector(v)
        if self.objective.prior is not None:
            hv = hv + self.objective.prior.hessian_vector(v)
        return hv

    # -- swept (stacked λ-lane) surface ------------------------------------

    def _lane_reg(self, W: Array, reg: SweptRegularization | None,
                  method: str) -> Array:
        """Per-lane L2/prior term via the named context method —
        [L(, d)].  ``reg`` None applies the objective's own weight to
        every lane."""
        ctx = self.objective.reg
        if reg is None:
            out = jax.vmap(getattr(ctx, method))(W)
        else:
            out = jax.vmap(
                lambda w, l2: getattr(ctx.replace(l2_weight=l2), method)(w)
            )(W, reg.l2_weights)
        return out

    def value_swept(self, W: Array,
                    reg: SweptRegularization | None = None) -> Array:
        """[L, d] stacked lanes → [L] values from ONE chunk sweep."""
        W = jnp.asarray(W, jnp.float32)
        val = self._sweep(
            lambda b: _jit_val_swept(self._inner, W, b, self._lane_map),
            lambda a, x: a + x,
            cost=("chunk_value_swept", _jit_val_swept,
                  lambda b: (self._inner, W, b, self._lane_map)),
            zero=lambda: jnp.zeros((W.shape[0],), jnp.float32))
        val = val + self._lane_reg(W, reg, "l2_value")
        if self.objective.prior is not None:
            val = val + jax.vmap(self.objective.prior.value)(W)
        return val

    def value_and_gradient_swept(
        self, W: Array, reg: SweptRegularization | None = None,
    ) -> tuple[Array, Array]:
        """[L, d] stacked lanes → ([L], [L, d]) from ONE double-buffered
        chunk sweep: the λ grid's L data passes collapse to one, since
        the per-chunk partials are λ-independent and per-lane reg is
        added here, outside the chunk loop."""
        W = jnp.asarray(W, jnp.float32)
        f, g = self._sweep(
            lambda b: _jit_vg_swept(self._inner, W, b, self._lane_map),
            lambda a, x: (a[0] + x[0], a[1] + x[1]),
            cost=("chunk_vg_swept", _jit_vg_swept,
                  lambda b: (self._inner, W, b, self._lane_map)),
            zero=lambda: (jnp.zeros((W.shape[0],), jnp.float32),
                          jnp.zeros_like(W)))
        f = f + self._lane_reg(W, reg, "l2_value")
        g = g + self._lane_reg(W, reg, "l2_gradient")
        if self.objective.prior is not None:
            f = f + jax.vmap(self.objective.prior.value)(W)
            g = g + jax.vmap(self.objective.prior.gradient)(W)
        return f, g

    def _per_example(self, fn) -> np.ndarray:
        """Concatenate a per-chunk per-example quantity over all chunks
        — [n] host array (n·f32 stays bounded; only plans/features were
        too big for residency).  Each chunk's D2H copy is STARTED
        asynchronously as soon as its compute is dispatched, so copies
        overlap the next chunk's compute; the blocking ``np.asarray``
        conversions happen once at the end, when most bytes have
        already landed (a serial per-chunk ``np.asarray`` would fence
        every chunk).  The chunk feed is the same pipelined
        ``_chunk_stream`` the objective sweeps use — spill-store
        batches prefetch disk→host→device here too (scoring sweeps are
        a full data pass like any other)."""
        pending = []
        bounded = self.batch.store is not None
        fred = _fleet_reducer()
        telemetry.count("solver.per_example_passes")
        steps = len(self.batch.chunk_schedule)
        with telemetry.span("per_example_pass", cat="solver",
                            chunks=self.batch.n_chunks):
            for ci, (cid, cur) in enumerate(self._chunk_stream()):
                if cid < 0:   # ragged-shard sentinel: nothing to score
                    _mon.progress("train.pass", ci + 1, steps,
                                  unit="chunks")
                    continue
                with telemetry.span("chunk_compute", cat="device"):
                    if bounded and pending:
                        # Backpressure (see _sweep): chunk i-1's compute
                        # must retire before chunk i dispatches, or
                        # every placed chunk stays live in the dispatch
                        # queue.  Only the [rows]-sized margins are
                        # fenced — their async D2H copies keep
                        # overlapping later chunks' compute.
                        jax.block_until_ready(pending[-1][0])
                    m = fn(cur)
                try:
                    m.copy_to_host_async()
                except AttributeError:  # photon-lint: disable=swallowed-exception (backends without async D2H: the device_get below copies synchronously)
                    pass
                lo, hi = self.batch.chunk_slice(cid)
                pending.append((m, cid, hi - lo))
                _mon.progress("train.pass", ci + 1, steps,
                              unit="chunks")
            if fred is None:
                if not pending:
                    return np.zeros(0, np.float32)
                # device_get, not np.asarray: the harvest is a PLANNED
                # device-to-host copy, and the explicit spelling keeps
                # it allowed under guards.no_implicit_transfers (the
                # async copies above already landed most bytes; this
                # just materializes).
                return np.concatenate(
                    [jax.device_get(m)[:rows] for m, _, rows in pending])
            # Fleet: scatter this host's chunk slices into the full
            # [n] plane and sum across hosts ONCE at the end (each
            # example is owned by exactly one host, so the sum IS the
            # concatenation) — per-example planes take one barrier per
            # pass, not one per chunk.
            full = np.zeros(self.batch.n, np.float32)
            for m, cid, rows in pending:
                lo, _hi = self.batch.chunk_slice(cid)
                full[lo:lo + rows] = jax.device_get(m)[:rows]
            return np.asarray(fred.reduce(full))

    def predict_margins(self, w: Array) -> np.ndarray:
        """Per-example margins (offsets included) over all chunks."""
        w = jnp.asarray(w, jnp.float32)
        return self._per_example(
            lambda b: _jit_margins(self._inner, w, b))

    def x_dot(self, w: Array) -> np.ndarray:
        """Raw X·w per example (offset-free scoring, the GAME
        ``CoordinateDataScores`` convention)."""
        w = jnp.asarray(w, jnp.float32)
        if self._mesh is not None:
            return self._per_example(
                lambda b: _jit_xdot_obj(self._inner, w, b))
        return self._per_example(lambda b: _jit_xdot(w, b))


def _tracker_state(tracker) -> dict:
    """StatesTracker → checkpoint tree (None planes pass through)."""
    return {"values": tracker.values, "grad_norms": tracker.grad_norms,
            "count": tracker.count, "step_sizes": tracker.step_sizes,
            "ls_trials": tracker.ls_trials}


def _restore_tracker(st: dict):
    from photon_ml_tpu.optim.base import StatesTracker

    opt = lambda a: None if a is None else jnp.asarray(a, jnp.float32)
    return StatesTracker(
        values=jnp.asarray(st["values"], jnp.float32),
        grad_norms=jnp.asarray(st["grad_norms"], jnp.float32),
        count=jnp.asarray(st["count"], jnp.int32),
        step_sizes=opt(st.get("step_sizes")),
        ls_trials=opt(st.get("ls_trials")),
    )


def _fleet_seq() -> int:
    """The fleet reducer's reduction counter for checkpoint trees
    (-1 outside a fleet).  A resumed host restores it and REPLAYS its
    reduce sequence — the coordinator answers already-completed
    sequence numbers from its result cache, so the replay fast-forwards
    to the live barrier the rest of the fleet is blocked on."""
    red = _fleet_reducer()
    return -1 if red is None else int(red.seq)


def _restore_fleet_seq(seq) -> None:
    if seq is None or int(seq) < 0:
        return
    red = _fleet_reducer()
    if red is not None:
        red.seq = int(seq)
        telemetry.count("fleet.seq_restored")


def _solver_checkpoint(solver_name: str, label: str):
    """(checkpointer, scoped label) when an active checkpoint session
    has mid-solve cadence enabled, else (None, None) — the solvers'
    one hook into ``reliability.checkpoint`` (ISSUE 9)."""
    ck = _ckpt.active()
    if ck is None or ck.every_solver_iters <= 0:
        return None, None
    name = solver_name + (f":{label}" if label else "")
    return ck, ck.solver_label(name)


def _solver_fingerprint(m: int, *arrays) -> str:
    """Identity stamp for a mid-solve snapshot: the warm start and l1
    weights pin the (objective, position) lineage — a resumed process
    reconstructs both bitwise from the CD/stage checkpoints, while an
    edited config (new λ grid at the same lane count, changed warm
    path) produces different bytes, so a stale snapshot is REJECTED
    instead of silently adopted (review finding: the scope label alone
    cannot tell two configs apart).  ``m`` guards the (s, y) buffer
    geometry."""
    h = hashlib.blake2b(digest_size=16)
    h.update(str(int(m)).encode())
    for a in arrays:
        if a is None:
            h.update(b"|none")
        else:
            arr = np.asarray(a, np.float32)
            h.update(f"|{arr.shape}".encode())
            h.update(arr.tobytes())
    return h.hexdigest()


def streaming_lbfgs_solve(
    value_and_grad,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weight=None,
    value_fn=None,
    label: str = "",
) -> OptimizationResult:
    """Host-driven L-BFGS / OWL-QN over an expensive (streamed)
    ``value_and_grad`` — the chunked mirror of ``optim.lbfgs
    .lbfgs_solve`` (same math, same convergence semantics; the outer
    loop is Python because each evaluation swaps chunks through HBM).

    ``value_fn`` (optional, ``w → f``) makes backtracking cheaper: the
    FIRST line-search trial keeps the fused value+gradient pass (the
    steady state accepts α=1, so the common case stays one pass per
    iteration), later trials run value-only passes, and the gradient is
    computed once on the accepted point — every rejected backtrack
    stops paying the gradient half of its pass.
    """
    m = config.lbfgs_memory
    w = jnp.asarray(w0, jnp.float32)
    owlqn = l1_weight is not None
    solver_name = "streaming_owlqn" if owlqn else "streaming_lbfgs"
    l1 = (jnp.broadcast_to(jnp.asarray(l1_weight, w.dtype), w.shape)
          if owlqn else None)

    def l1_term(w_):
        return jnp.sum(l1 * jnp.abs(w_)) if owlqn else 0.0

    def full_value_grad(w_):
        f, g = value_and_grad(w_)
        return f + l1_term(w_), g

    full_value = (None if value_fn is None
                  else (lambda w_: value_fn(w_) + l1_term(w_)))

    def pgrad(g_, w_):
        return _pseudo_gradient(g_, w_, l1) if owlqn else g_

    ck, ck_label = _solver_checkpoint(solver_name, label)
    fp = _solver_fingerprint(m, w, l1) if ck is not None else None
    restored = ck.load_solver(ck_label) if ck is not None else None
    if restored is not None and restored.get("fp") != fp:
        logger.warning(
            "streaming lbfgs '%s': solver snapshot ignored — "
            "objective/warm-start fingerprint mismatch (config changed "
            "since the interrupted run?)", label)
        restored = None
    if restored is not None:
        # Mid-solve resume (ISSUE 9): the loop re-enters at the exact
        # iteration boundary the snapshot captured — committed point,
        # value, gradient, and the full (s, y, ρ) memory — so the
        # continuation is the run the kill interrupted.  The initial
        # fused evaluation is NOT repaid (and not counted: the resumed
        # process never streamed it).
        telemetry.count("solver.resumed_solves")
        w = jnp.asarray(restored["w"], jnp.float32)
        f = jnp.asarray(restored["f"], jnp.float32)
        g = jnp.asarray(restored["g"], jnp.float32)
        pg = pgrad(g, w)
        g0_norm = float(restored["g0_norm"])
        s_hist = [jnp.asarray(s, jnp.float32)
                  for s in restored["s_hist"]]
        y_hist = [jnp.asarray(y, jnp.float32)
                  for y in restored["y_hist"]]
        rho_hist = [float(r) for r in restored["rho_hist"]]
        tracker = _restore_tracker(restored["tracker"])
        converged = bool(restored["converged"])
        it = int(restored["it"])
        _restore_fleet_seq(restored.get("fleet_seq"))
        logger.info("streaming lbfgs '%s': resumed at iteration %d",
                    label, it)
    else:
        # Sweep-odometer accounting (ISSUE 8): the initial fused
        # evaluation below is the one data pass neither an ls_trial nor
        # a recovery counter claims — one tick per solve closes the
        # identity
        #   solver.sweeps == streamed_solves + ls_trials
        #                    + grad_recovery_sweeps + aux_sweeps
        # that `telemetry report` reconciles.
        telemetry.count("solver.streamed_solves")
        f, g = full_value_grad(w)
        pg = pgrad(g, w)
        g0_norm = float(jnp.linalg.norm(pg))
        tracker = StatesTracker.create(config.max_iters)
        if config.track_states:
            tracker = tracker.record(jnp.asarray(0, jnp.int32), f,
                                     jnp.asarray(g0_norm))
        s_hist = []   # newest first
        y_hist = []
        rho_hist = []
        converged = bool(grad_converged(jnp.asarray(g0_norm),
                                        jnp.asarray(g0_norm),
                                        config.tolerance))
        it = 0
    while not converged and it < config.max_iters:
        # Two-loop recursion over the (s, y) history.
        q = pg
        alphas = []
        for s, y, rho in zip(s_hist, y_hist, rho_hist):
            a = rho * jnp.vdot(s, q)
            alphas.append(a)
            q = q - a * y
        if s_hist:
            y_new = y_hist[0]
            gamma = 1.0 / jnp.maximum(
                rho_hist[0] * jnp.vdot(y_new, y_new), _CURVATURE_EPS)
        else:
            gamma = 1.0
        r = gamma * q
        for (s, y, rho), a in zip(reversed(list(zip(s_hist, y_hist,
                                                    rho_hist))),
                                  reversed(alphas)):
            beta = rho * jnp.vdot(y, r)
            r = r + s * (a - beta)
        d = -r
        if owlqn:
            d = jnp.where(d * -pg > 0.0, d, 0.0)
            xi = jnp.where(w != 0.0, jnp.sign(w), jnp.sign(-pg))
        # Steepest-descent safeguard on numerical breakdown.
        if float(jnp.vdot(pg, d)) >= 0.0:
            d = -pg

        # Backtracking Armijo (modified condition under the orthant
        # projection — identical to optim.lbfgs._line_search).
        # Backtracking mirror of optim.lbfgs._line_search: on Armijo
        # accept the trial commits; after ls_max_steps backtracks the
        # LAST trial commits anyway (the resident while_loop exits with
        # it), and in both cases only a STRICT decrease counts as
        # progress (ok = f_new < f0) — a zero-decrease step means
        # progress is below f32 measurement precision and the solve
        # stall-terminates rather than grinds.
        alpha = 1.0
        g_try = None
        trials = 0
        for step in range(config.ls_max_steps + 1):
            # The step the committed trial actually used: on a range
            # exhaustion the loop tail shrinks ``alpha`` AFTER building
            # w_try, so recording ``alpha`` there would understate the
            # terminal stall-edge step by one shrink factor.
            alpha_used = alpha
            w_try = w + alpha * d
            if owlqn:
                w_try = jnp.where(jnp.sign(w_try) == xi, w_try, 0.0)
            telemetry.count("solver.ls_trials")
            trials += 1
            if step == 0 or full_value is None:
                f_try, g_try = full_value_grad(w_try)
            else:
                f_try, g_try = full_value(w_try), None
            if float(f_try) <= float(
                    f + config.ls_c1 * jnp.vdot(pg, w_try - w)):
                break
            alpha *= config.ls_shrink
        if g_try is None and float(f_try) < float(f):
            # Accepted (or committed) a value-only trial that will
            # take effect: one fused pass recovers its gradient.  A
            # stall (no strict decrease — the common terminal
            # iteration) keeps the old state, so its gradient would be
            # discarded work: skip the pass and terminate below.
            telemetry.count("solver.grad_recovery_sweeps")
            f_try, g_try = full_value_grad(w_try)
        elif g_try is None:
            g_try = g   # stalled: state is not committed below
        w_new, f_new, g_new = w_try, f_try, g_try
        ls_ok = float(f_new) < float(f)
        if ls_ok:
            s = w_new - w
            y = g_new - g
            sy = float(jnp.vdot(s, y))
            if sy > _CURVATURE_EPS * float(
                    jnp.linalg.norm(s) * jnp.linalg.norm(y)):
                s_hist.insert(0, s)
                y_hist.insert(0, y)
                rho_hist.insert(0, 1.0 / max(sy, _CURVATURE_EPS))
                del s_hist[m:], y_hist[m:], rho_hist[m:]

        pg_new = pgrad(g_new, w_new)
        g_norm = jnp.linalg.norm(pg_new)
        conv = bool(grad_converged(g_norm, jnp.asarray(g0_norm),
                                   config.tolerance)) or bool(
            loss_converged(f_new, f, config.rel_tolerance))
        stalled = not ls_ok   # no measurable decrease possible
        it += 1
        telemetry.count("solver.iterations")
        if config.track_states:
            tracker = tracker.record(jnp.asarray(it, jnp.int32),
                                     f_new, g_norm,
                                     step_size=jnp.asarray(
                                         alpha_used if ls_ok else 0.0),
                                     ls_trials=jnp.asarray(
                                         float(trials)))
        _conv.iteration(solver_name, label, it, float(f_new),
                        float(g_norm),
                        step_size=(alpha_used if ls_ok else 0.0),
                        ls_trials=trials)
        # Live solver progress (ISSUE 10): iteration count against the
        # budget plus the loss the online divergence rules watch.
        _mon.progress("solver" + (f".{label}" if label else ""),
                      it, config.max_iters, unit="iters",
                      loss=float(f_new), grad_norm=float(g_norm))
        logger.info("streaming lbfgs iter %d: f=%.6f |pg|=%.3e%s", it,
                    float(f_new), float(g_norm),
                    " (stalled)" if stalled else "")
        if ls_ok:
            w, f, g, pg = w_new, f_new, g_new, pg_new
        converged = conv or stalled
        if ck is not None:
            # Iteration-boundary snapshot (cadence-gated): everything
            # the resumed loop needs to continue bit-for-bit.
            ck.maybe_save_solver(ck_label, it, {
                "fp": fp,
                "w": w, "f": f, "g": g, "g0_norm": float(g0_norm),
                "s_hist": list(s_hist), "y_hist": list(y_hist),
                "rho_hist": [float(r) for r in rho_hist],
                "converged": bool(converged),
                "tracker": _tracker_state(tracker),
                "fleet_seq": _fleet_seq(),
            })

    if ck is not None:
        ck.clear_solver(ck_label)   # superseded by the result
    pg_f = pgrad(g, w)
    result = OptimizationResult(
        w=w,
        value=f,
        grad_norm=jnp.linalg.norm(pg_f),
        iterations=jnp.asarray(it, jnp.int32),
        converged=jnp.asarray(converged),
        tracker=tracker,
    )
    _conv.solve_trace(solver_name, label, result)
    return result


def streaming_tron_solve(
    value_and_grad,
    hvp,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    hessian_diag=None,
    label: str = "",
) -> OptimizationResult:
    """Host-driven trust-region Newton over a chunk-streamed objective
    — the out-of-core mirror of ``optim.tron.tron_solve`` (ISSUE 17).

    Same math as the resident solver (Steihaug CG inside the Lin–Moré
    radius schedule, identical accept/shrink constants), but both loops
    run on the host because every Hessian-vector product is a full
    chunk-streamed data pass: ``hvp(w, v)`` is
    ``ChunkedGLMObjective.hvp_pass`` — one module-jitted per-chunk
    program accumulating J^T D J v partials, fleet psum-reduced per
    chunk, accounted under ``solver.hvp_sweeps``.

    ``hessian_diag`` (optional, ``w → diag H(w)``) enables Jacobi
    preconditioning: one aux pass at the warm start buys the diagonal,
    CG then runs in the scaled space p̂ = D^{1/2} p with the trust
    region measuring ‖p̂‖ (the LIBLINEAR 2.20 convention) — this
    collapses the CG iteration count on badly feature-scaled problems,
    exactly the ill-conditioned regime TRON exists for.  The
    preconditioner is FROZEN for the whole solve (any fixed SPD scaling
    is a valid preconditioner; freshness affects CG speed, never the
    answer) and rides the snapshot tree so resumes stay bitwise.

    The predicted reduction is recovered incrementally from the CG
    residual (prered = ½(p̂ᵀr̂ − ĝᵀp̂), with r̂ kept consistent on the
    boundary exits) — no dedicated H·p̂ pass, so an outer iteration
    costs exactly ``cg_iters`` HVP passes plus one trial evaluation.

    Mid-CG resume (ISSUE 9 semantics): with solver-iteration
    checkpointing enabled a snapshot is cut after every CG step — the
    CG basis vectors (p̂, r̂, d̂, rs), trust radius, and outer (w, f, g)
    all ride the state tree, fingerprinted like the L-BFGS snapshots —
    so a SIGKILL inside the inner loop resumes at the exact HVP
    boundary and reproduces the uninterrupted fit bitwise.
    """
    w = jnp.asarray(w0, jnp.float32)
    solver_name = "streaming_tron"

    ck, ck_label = _solver_checkpoint(solver_name, label)
    fp = (_solver_fingerprint(config.cg_max_iters, w)
          if ck is not None else None)
    restored = ck.load_solver(ck_label) if ck is not None else None
    if restored is not None and restored.get("fp") != fp:
        logger.warning(
            "streaming tron '%s': solver snapshot ignored — "
            "objective/warm-start fingerprint mismatch (config changed "
            "since the interrupted run?)", label)
        restored = None
    cg_state = None
    if restored is not None:
        # Mid-solve resume: the loop re-enters at the exact snapshot
        # boundary — outer point, radius, and (mid-CG) the basis
        # vectors — so the continuation is the run the kill
        # interrupted.  The initial fused evaluation (and the
        # preconditioner pass) are NOT repaid and not counted.
        telemetry.count("solver.resumed_solves")
        w = jnp.asarray(restored["w"], jnp.float32)
        f = jnp.asarray(restored["f"], jnp.float32)
        g = jnp.asarray(restored["g"], jnp.float32)
        delta = float(restored["delta"])
        g0_norm = float(restored["g0_norm"])
        scale = (None if restored.get("scale") is None
                 else jnp.asarray(restored["scale"], jnp.float32))
        tracker = _restore_tracker(restored["tracker"])
        converged = bool(restored["converged"])
        it = int(restored["it"])
        steps = int(restored["steps"])
        cg = restored.get("cg")
        if cg is not None:
            cg_state = (jnp.asarray(cg["p"], jnp.float32),
                        jnp.asarray(cg["r"], jnp.float32),
                        jnp.asarray(cg["d"], jnp.float32),
                        jnp.asarray(cg["rs"], jnp.float32),
                        int(cg["cg_it"]))
        _restore_fleet_seq(restored.get("fleet_seq"))
        logger.info(
            "streaming tron '%s': resumed at iteration %d%s", label, it,
            f" (mid-CG, step {cg_state[4]})" if cg_state else "")
    else:
        # Sweep-odometer accounting (ISSUE 8): the initial fused
        # evaluation is the one pass the streamed_solves tick claims;
        # CG passes ride hvp_sweeps, trial evaluations ride ls_trials,
        # and the preconditioner diagonal rides aux_sweeps — together
        # they close the identity `telemetry report` reconciles.
        telemetry.count("solver.streamed_solves")
        f, g = value_and_grad(w)
        scale = None
        if hessian_diag is not None:
            diag = hessian_diag(w)
            scale = 1.0 / jnp.sqrt(jnp.maximum(
                jnp.asarray(diag, jnp.float32), 1e-12))
        g0_norm = float(jnp.linalg.norm(g))
        delta = float(jnp.linalg.norm(g if scale is None else scale * g))
        tracker = StatesTracker.create(config.max_iters)
        if config.track_states:
            tracker = tracker.record(jnp.asarray(0, jnp.int32), f,
                                     jnp.asarray(g0_norm))
        converged = bool(grad_converged(jnp.asarray(g0_norm),
                                        jnp.asarray(g0_norm),
                                        config.tolerance))
        it = 0
        steps = 0

    def save(cg):
        """Cadence-gated snapshot at the current (outer, CG) boundary.
        ``steps`` counts HVP passes + outer commits, so the configured
        ``every_solver_iters`` cadence lands INSIDE long CG solves."""
        if ck is None:
            return
        ck.maybe_save_solver(ck_label, steps, {
            "fp": fp, "w": w, "f": f, "g": g,
            "delta": float(delta), "g0_norm": float(g0_norm),
            "scale": scale, "it": it, "steps": steps,
            "converged": bool(converged),
            "tracker": _tracker_state(tracker),
            "fleet_seq": _fleet_seq(),
            "cg": cg,
        })

    while not converged and it < config.max_iters:
        g_hat = g if scale is None else scale * g
        tol_cg = config.cg_tolerance * float(jnp.linalg.norm(g_hat))
        if cg_state is not None:
            p, r, d, rs, cg_it = cg_state
            cg_state = None
        else:
            p = jnp.zeros_like(g_hat)
            r = -g_hat
            d = r
            rs = jnp.vdot(r, r)
            cg_it = 0
        # -- Steihaug-CG inner loop: one chunked HVP pass per step ----
        while (cg_it < config.cg_max_iters
               and float(jnp.sqrt(rs)) > tol_cg):
            hd = (hvp(w, d) if scale is None
                  else scale * hvp(w, scale * d))
            dhd = jnp.vdot(d, hd)
            cg_it += 1
            steps += 1
            if float(dhd) <= 0.0:
                # Negative/zero curvature: march to the boundary, and
                # keep the residual consistent (r̂ ← r̂ − τ·Ĥd̂) so the
                # incremental predicted-reduction identity below stays
                # exact without a dedicated H·p̂ pass.
                tau = _boundary_tau(p, d, delta)
                p = p + tau * d
                r = r - tau * hd
                break
            alpha = rs / jnp.maximum(dhd, 1e-30)
            p_try = p + alpha * d
            if float(jnp.linalg.norm(p_try)) >= delta:
                tau = _boundary_tau(p, d, delta)
                p = p + tau * d
                r = r - tau * hd
                break
            p = p_try
            r = r - alpha * hd
            rs_new = jnp.vdot(r, r)
            beta = rs_new / jnp.maximum(rs, 1e-30)
            d = r + beta * d
            rs = rs_new
            save({"p": p, "r": r, "d": d, "rs": rs, "cg_it": cg_it})

        predicted = float(0.5 * (jnp.vdot(p, r) - jnp.vdot(g_hat, p)))
        step = p if scale is None else scale * p
        w_try = w + step
        # Trial-point evaluation: accounted like a line-search trial
        # (accept/reject against the model's predicted reduction).
        telemetry.count("solver.ls_trials")
        f_new, g_new = value_and_grad(w_try)
        f_prev = f
        actual = float(f) - float(f_new)
        rho = actual / max(predicted, 1e-30)
        accept = (rho > _ETA0) and (actual > 0.0)
        p_norm = float(jnp.linalg.norm(p))   # trust-region (scaled) norm
        # Radius update (Lin & Moré simplified schedule, as resident):
        if rho < _SIGMA1:
            delta = min(delta, p_norm) * _SIGMA1
        elif rho > 0.75:
            delta = max(delta, _SIGMA3 * p_norm / 2.0)
        delta = max(delta, _DELTA_MIN)

        if accept:
            w, f, g = w_try, f_new, g_new
        g_norm = float(jnp.linalg.norm(g))
        conv = bool(grad_converged(jnp.asarray(g_norm),
                                   jnp.asarray(g0_norm),
                                   config.tolerance))
        if accept and bool(loss_converged(f_new, f_prev,
                                          config.rel_tolerance)):
            conv = True
        # Numerical-precision stop (mirrors the resident solver): when
        # the model predicts less reduction than f32 can measure on
        # |f|, further iterations only reject steps and shrink Δ.
        if predicted <= 1e-6 * max(abs(float(f_prev)), 1.0):
            conv = True
        stalled = delta <= _DELTA_MIN
        it += 1
        steps += 1
        telemetry.count("solver.iterations")
        if config.track_states:
            tracker = tracker.record(
                jnp.asarray(it, jnp.int32), f, jnp.asarray(g_norm),
                step_size=jnp.asarray(p_norm if accept else 0.0),
                ls_trials=jnp.asarray(float(cg_it)))
        _conv.iteration(solver_name, label, it, float(f), g_norm,
                        step_size=(p_norm if accept else 0.0),
                        ls_trials=cg_it, delta=delta, rho=rho)
        # Live solver progress (ISSUE 10): the `train.tron` monitor
        # stage — iteration count against the budget plus the loss the
        # online divergence rules watch.
        _mon.progress("train.tron" + (f".{label}" if label else ""),
                      it, config.max_iters, unit="iters",
                      loss=float(f), grad_norm=g_norm)
        logger.info(
            "streaming tron iter %d: f=%.6f |g|=%.3e delta=%.3e "
            "rho=%.3f cg=%d%s", it, float(f), g_norm, delta, rho,
            cg_it, "" if accept else " (rejected)")
        converged = conv
        save(None)
        if stalled:
            break

    if ck is not None:
        ck.clear_solver(ck_label)   # superseded by the result
    result = OptimizationResult(
        w=w,
        value=f,
        grad_norm=jnp.linalg.norm(g),
        iterations=jnp.asarray(it, jnp.int32),
        converged=jnp.asarray(converged),
        tracker=tracker,
    )
    _conv.solve_trace(solver_name, label, result)
    return result


def streaming_lbfgs_solve_swept(
    value_and_grad_swept,
    value_swept,
    w0s: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weights=None,
    label: str = "",
) -> OptimizationResult:
    """Host-driven batched-lane L-BFGS / OWL-QN: the whole λ grid as
    ONE streamed solve.

    The chunked mirror of ``optim.lbfgs.lbfgs_solve_swept``: all
    per-lane state (coefficients, (s, y) circular buffers, line-search
    step sizes, convergence flags) carries a leading lane axis L and
    every update is masked per lane, so converged lanes coast while
    stragglers finish — and EVERY objective evaluation is one shared
    chunk sweep feeding all L lanes (``value_and_grad_swept``:
    ``W [L, d] → (F [L], G [L, d])`` including per-lane smooth reg).
    Data passes per solver iteration drop from L (sequential fits) to
    ~1: one fused value+gradient sweep when every searching lane
    accepts α=1 (the steady state), plus one shared value-only sweep
    per extra backtracking trial (``value_swept``) and one gradient
    recovery sweep on iterations where some lane accepted late.

    ``l1_weights``: None, [L] per-lane scalars, or [L, d] per-lane
    vectors — any non-None activates OWL-QN semantics on every lane.

    Returns a batched ``OptimizationResult`` (leading dim L), like a
    vmapped resident solve.
    """
    m = config.lbfgs_memory
    W = jnp.asarray(w0s, jnp.float32)
    L, d = W.shape
    owlqn = l1_weights is not None
    solver_name = ("streaming_owlqn_swept" if owlqn
                   else "streaming_lbfgs_swept")
    if owlqn:
        l1 = jnp.asarray(l1_weights, W.dtype)
        l1 = jnp.broadcast_to(l1.reshape(L, -1), (L, d))

    def l1_term(W_):
        return jnp.sum(l1 * jnp.abs(W_), axis=-1) if owlqn else 0.0

    def full_vg(W_):
        F_, G_ = value_and_grad_swept(W_)
        return F_ + l1_term(W_), G_

    def full_val(W_):
        return value_swept(W_) + l1_term(W_)

    def pgrad(G_, W_):
        return _pseudo_gradient(G_, W_, l1) if owlqn else G_

    ck, ck_label = _solver_checkpoint(solver_name, label)
    fp = (_solver_fingerprint(m, W, l1 if owlqn else None)
          if ck is not None else None)
    restored = ck.load_solver(ck_label) if ck is not None else None
    if restored is not None and restored.get("fp") != fp:
        logger.warning(
            "streaming swept lbfgs '%s': solver snapshot ignored — "
            "objective/warm-start fingerprint mismatch (λ grid or "
            "warm path changed since the interrupted run?)", label)
        restored = None
    if restored is not None:
        # Mid-solve resume of the whole masked-lane state (ISSUE 9):
        # λ-sweep lane coefficients, per-lane (s, y, ρ) circular
        # buffers, convergence masks, tracker planes.
        telemetry.count("solver.resumed_solves")
        W = jnp.asarray(restored["W"], jnp.float32)
        F = jnp.asarray(restored["F"], jnp.float32)
        G = jnp.asarray(restored["G"], jnp.float32)
        g0_norm = jnp.asarray(restored["g0_norm"], jnp.float32)
        done = jnp.asarray(restored["done"], bool)
        converged = jnp.asarray(restored["converged"], bool)
        iters = jnp.asarray(restored["iters"], jnp.int32)
        S_buf = jnp.asarray(restored["S_buf"], W.dtype)
        Y_buf = jnp.asarray(restored["Y_buf"], W.dtype)
        Rho = jnp.asarray(restored["Rho"], W.dtype)
        head = jnp.asarray(restored["head"], jnp.int32)
        count = jnp.asarray(restored["count"], jnp.int32)
        t_vals = jnp.asarray(restored["t_vals"], jnp.float32)
        t_gn = jnp.asarray(restored["t_gn"], jnp.float32)
        it = int(restored["it"])
        _restore_fleet_seq(restored.get("fleet_seq"))
        logger.info("streaming swept lbfgs '%s': resumed at iteration "
                    "%d (%d/%d lanes done)", label, it,
                    int(jnp.sum(done)), L)
    else:
        # One tick per solve for the initial fused sweep — see the
        # odometer identity note in streaming_lbfgs_solve.
        telemetry.count("solver.streamed_solves")
        F, G = full_vg(W)
        PG = pgrad(G, W)
        g0_norm = jnp.linalg.norm(PG, axis=-1)                    # [L]
        done = grad_converged(g0_norm, g0_norm, config.tolerance)  # [L]
        converged = done
        iters = jnp.zeros((L,), jnp.int32)

        S_buf = jnp.zeros((m, L, d), W.dtype)
        Y_buf = jnp.zeros((m, L, d), W.dtype)
        Rho = jnp.zeros((m, L), W.dtype)
        head = jnp.zeros((L,), jnp.int32)
        count = jnp.zeros((L,), jnp.int32)

        t_vals = jnp.full((L, config.max_iters + 1), jnp.nan,
                          jnp.float32)
        t_gn = jnp.full((L, config.max_iters + 1), jnp.nan, jnp.float32)
        if config.track_states:
            t_vals = t_vals.at[:, 0].set(F)
            t_gn = t_gn.at[:, 0].set(g0_norm)

        it = 0
    while not bool(jnp.all(done)) and it < config.max_iters:
        active = jnp.logical_not(done)
        PG = pgrad(G, W)

        # Per-lane two-loop recursion + OWL-QN projections, one
        # dispatch (module-level jit).
        D, Xi = _swept_direction(PG, W, S_buf, Y_buf, Rho, head, count,
                                 l1 if owlqn else None)

        def project(W_try):
            if not owlqn:
                return W_try
            return jnp.where(jnp.sign(W_try) == Xi, W_try, 0.0)

        def armijo(W_t, F_t):
            return F_t <= F + config.ls_c1 * jnp.sum(
                PG * (W_t - W), axis=-1)

        # Batched backtracking: one SHARED sweep per trial serves every
        # still-searching lane.  Trial 0 is the fused value+gradient
        # sweep (steady state: all lanes accept α=1 → one pass per
        # iteration for the whole grid); later trials are value-only.
        alpha = jnp.ones((L,), W.dtype)
        W_try = project(W + alpha[:, None] * D)
        telemetry.count("solver.ls_trials")
        trials = 1
        F1, G1 = full_vg(W_try)
        ok = armijo(W_try, F1)
        accepted = ok | done
        commit0 = ok & active
        W_acc = jnp.where(commit0[:, None], W_try, W)
        F_acc = jnp.where(commit0, F1, F)
        G_acc = jnp.where(commit0[:, None], G1, G)
        grad_known = accepted          # lanes whose G_acc is current
        W_last, F_last = W_try, F1
        for _ in range(config.ls_max_steps):
            if bool(jnp.all(accepted)):
                break
            alpha = jnp.where(accepted, alpha, alpha * config.ls_shrink)
            W_try = project(W + alpha[:, None] * D)
            # Accepted lanes re-evaluate at their committed point (the
            # sweep is shared; their rows are simply ignored).
            W_eval = jnp.where(accepted[:, None], W_acc, W_try)
            telemetry.count("solver.ls_trials")
            trials += 1
            F_eval = full_val(W_eval)
            ok = armijo(W_eval, F_eval) & jnp.logical_not(accepted)
            W_acc = jnp.where(ok[:, None], W_try, W_acc)
            F_acc = jnp.where(ok, F_eval, F_acc)
            accepted = accepted | ok
            still = jnp.logical_not(accepted)
            W_last = jnp.where(still[:, None], W_try, W_last)
            F_last = jnp.where(still, F_eval, F_last)
        # Never-accepted lanes commit the LAST trial (resident
        # semantics); only a strict decrease counts as progress below.
        hold = accepted | jnp.logical_not(active)
        W_new = jnp.where(hold[:, None], W_acc, W_last)
        F_new = jnp.where(hold, F_acc, F_last)
        # Gradient recovery is only owed to lanes that BOTH moved past
        # trial 0 and will actually commit (strict decrease) — a lane
        # that exhausted its backtracks without progress stalls and
        # keeps its old state, so paying a sweep for its gradient would
        # be discarded work (stall iterations are common right at each
        # lane's convergence edge).
        need_grad = (jnp.logical_not(grad_known | done)
                     & (F_new < F) & active)
        if bool(jnp.any(need_grad)):
            # One shared sweep recovers every lane's gradient at its
            # committed point.
            telemetry.count("solver.grad_recovery_sweeps")
            F_new, G_new = full_vg(W_new)
        else:
            G_new = G_acc

        ls_ok = (F_new < F) & active
        s = W_new - W
        y = G_new - G
        sy = jnp.sum(s * y, axis=-1)
        good = ls_ok & (
            sy > _CURVATURE_EPS * jnp.linalg.norm(s, axis=-1)
            * jnp.linalg.norm(y, axis=-1))
        S_buf, Y_buf, Rho, head, count = _swept_push(
            S_buf, Y_buf, Rho, head, count, s, y, good)

        PG_new = pgrad(G_new, W_new)
        g_norm = jnp.linalg.norm(PG_new, axis=-1)
        conv = jnp.logical_or(
            grad_converged(g_norm, g0_norm, config.tolerance),
            loss_converged(F_new, F, config.rel_tolerance),
        )
        stalled = jnp.logical_not(ls_ok) & active
        it += 1
        telemetry.count("solver.iterations")
        iters = jnp.where(active, it, iters)
        if config.track_states:
            t_vals = t_vals.at[:, it].set(
                jnp.where(active, F_new, t_vals[:, it]))
            t_gn = t_gn.at[:, it].set(
                jnp.where(active, g_norm, t_gn[:, it]))
        # Commit per lane: line-search progress updates state; stalled
        # lanes keep theirs (and terminate, as in the resident solver).
        W = jnp.where(ls_ok[:, None], W_new, W)
        F = jnp.where(ls_ok, F_new, F)
        G = jnp.where(ls_ok[:, None], G_new, G)
        finished = active & (conv | stalled)
        converged = converged | finished
        done = done | finished
        _conv.iteration(solver_name, label, it, F, g_norm,
                        ls_trials=trials,
                        lanes_active=int(jnp.sum(active)),
                        lanes_done=int(jnp.sum(done)))
        _mon.progress("solver" + (f".{label}" if label else ""),
                      it, config.max_iters, unit="iters",
                      loss=float(jnp.min(F)),
                      lanes_done=int(jnp.sum(done)), lanes=L)
        logger.info(
            "streaming swept lbfgs iter %d: %d/%d lanes done, "
            "f_best=%.6f", it, int(jnp.sum(done)), L,
            float(jnp.min(F)))
        if ck is not None:
            ck.maybe_save_solver(ck_label, it, {
                "fp": fp,
                "W": W, "F": F, "G": G, "g0_norm": g0_norm,
                "done": done, "converged": converged, "iters": iters,
                "S_buf": S_buf, "Y_buf": Y_buf, "Rho": Rho,
                "head": head, "count": count,
                "t_vals": t_vals, "t_gn": t_gn,
                "fleet_seq": _fleet_seq(),
            })

    if ck is not None:
        ck.clear_solver(ck_label)   # superseded by the result
    PG_f = pgrad(G, W)
    tracker = StatesTracker(
        values=t_vals, grad_norms=t_gn,
        count=(iters + 1 if config.track_states
               else jnp.zeros((L,), jnp.int32)),
    )
    result = OptimizationResult(
        w=W,
        value=F,
        grad_norm=jnp.linalg.norm(PG_f, axis=-1),
        iterations=iters,
        converged=converged,
        tracker=tracker,
    )
    _conv.solve_trace(solver_name, label, result)
    return result
