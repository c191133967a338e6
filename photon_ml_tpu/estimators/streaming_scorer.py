"""Streaming fused inference: one-pass multi-coordinate scoring (ISSUE 4).

Reference counterpart: the reference scores with one Spark pass per
coordinate and a union of ``CoordinateDataScores`` RDDs — but Spark
streams partitions, so no executor ever holds the dataset.  The
round-4..8 rebuild gave *training* that shape (congruent chunk
programs, disk→host→device prefetch, bounded host window); this module
gives the same architecture to the serving half:

- **One pass, fixed-shape chunks**: the dataset is walked ONCE in
  ``chunk_rows``-row chunks (tail padded — one compile serves every
  chunk) instead of once per coordinate.
- **One fused device program per chunk** computes the fixed-effect ELL
  gather-dot AND every random effect's coefficient-row gather-dot,
  sums them into margins, and applies the task mean function — so
  mean-space predictions never round-trip a full ``[n]`` array through
  the device (ISSUE 4 satellite; the old driver uploaded the whole
  margins array just to sigmoid it).
- **Projected random effects** are inherently host-side (per-entity
  subspace merge-join); their per-chunk scores are folded into the
  chunk's ``base`` plane (offsets + host scores) before device
  dispatch, so the device program stays one fused sum.
- **Overlapped I/O**: chunks optionally spill through the round-8
  ``data.chunk_store`` (atomic content-keyed ``.npz``, memory-mapped
  loads, LRU ``host_max_resident`` window — spilled chunks double as a
  persistent warm-scoring artifact) and are fed by the round-8
  ``optim.streaming.ChunkPrefetcher`` thread: disk read → host staging
  → async ``device_put`` of chunks i+1..i+depth hide under chunk i's
  compute, with the same lag-2 dispatch backpressure so in-flight
  device buffers stay bounded at two chunks.
- **Streaming downstream**: a writer thread drains finished chunks
  into the output sinks (``io.score_sink``: incremental ``.npz``,
  block-per-chunk Avro) while ``evaluation.streaming`` accumulators
  fold the metrics — neither output nor evaluation ever holds the full
  dataset.

``GameTransformer.transform`` remains the per-coordinate resident path
(validation-sized data); this pipeline produces margins identical to it
up to float-summation order (device f32 chunk sums vs host f64 full
passes — tested to float tolerance on every coordinate mix).
"""

from __future__ import annotations

import dataclasses
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
from photon_ml_tpu.reliability import faults as _faults
from photon_ml_tpu.telemetry import monitor as _mon
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import TaskType

logger = logging.getLogger(__name__)

Array = jax.Array

# On-disk score-chunk format version (rides in the store key).
SCORE_CHUNK_VERSION = 1

# How many scored chunks may be in flight (dispatched, D2H copying)
# before the oldest is drained — two matches the device double-buffer
# everywhere else in the codebase.
_INFLIGHT = 2


@dataclasses.dataclass(frozen=True)
class _CoordSpec:
    """Static description of one coordinate's device-side scoring —
    the per-chunk program is specialized on the tuple of these."""

    name: str
    kind: str          # "fixed_sparse" | "fixed_dense" | "re"


@partial(jax.jit, static_argnums=(0, 1))
def _run_chunk(specs, mean_fn, tables, chunk):
    """THE fused per-chunk device program: every coordinate's
    contraction summed into margins + the task mean function, one
    dispatch per chunk.  Jitted at module level with the (hashable)
    spec tuple and mean function static, so every scorer instance for
    the same model STRUCTURE shares one compile — repeated scoring
    passes (driver re-runs in-process) never re-trace."""
    from photon_ml_tpu.ops.kernels import gather_rowsum

    m = chunk["base"]
    for s in specs:
        if s.kind == "fixed_sparse":
            m = m + gather_rowsum(
                tables[s.name], chunk[s.name + ".vals"],
                chunk[s.name + ".cols"]) + tables[s.name + ".base"]
        elif s.kind == "fixed_dense":
            m = m + chunk[s.name + ".x"] @ tables[s.name] \
                + tables[s.name + ".base"]
        else:   # re: coefficient-row gather-dot
            m = m + jnp.sum(
                chunk[s.name + ".x"]
                * tables[s.name][chunk[s.name + ".idx"]],
                axis=-1)
    return m, mean_fn(m)


class _SinkWriter:
    """Background writer thread: drains finished (host) chunks into the
    output sinks while the device scores later chunks.  Items are
    written in queue order (the main loop drains chunks in sweep order,
    so sinks see rows in order); errors surface at ``close``."""

    _SENTINEL = object()

    def __init__(self, sinks):
        self._sinks = list(sinks)
        self._q: queue.Queue = queue.Queue(maxsize=4)
        # _error crosses threads (written by the writer, read by the
        # producer mid-stream in put()), so it lives under a lock —
        # the photon-lint unlocked-shared-write contract.
        self._lock = threading.Lock()
        self._error: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="photon-score-writer")
        self._thread.start()

    def _failed(self) -> "BaseException | None":
        with self._lock:
            return self._error

    def _next_item(self):
        """Queue pop; with telemetry active, polls with liveness
        heartbeats so a starved (or hung-upstream) writer thread is
        visible in the run log."""
        t = telemetry.active()
        if t is None:
            # photon-lint: disable=eternal-wait (close() always enqueues the sentinel, and put() runs on the producer that also calls close(); the get is bounded by shutdown)
            return self._q.get()
        start = time.perf_counter()
        beat = start
        while True:
            try:
                return self._q.get(timeout=0.05)
            except queue.Empty:
                now = time.perf_counter()
                if now - beat >= t.heartbeat_s:
                    t.heartbeat("sink-writer", state="queue_empty",
                                waiting_s=round(now - start, 3))
                    beat = now

    def _run(self) -> None:
        while True:
            item = self._next_item()
            if item is self._SENTINEL:
                return
            if self._failed() is not None:
                continue       # drain without writing after a failure
            try:
                lo, hi, margins, preds, labels, ids = item
                t0 = time.perf_counter()
                with telemetry.span("sink_write", cat="sink",
                                    lo=lo, hi=hi):
                    _faults.fire("sink.write", lo=lo, hi=hi)
                    for s in self._sinks:
                        s.write(lo, hi, margins, preds, labels, ids=ids)
                telemetry.observe("sink.write_s",
                                  time.perf_counter() - t0)
            except BaseException as e:
                # Death event first (hung-run forensics), then the
                # locked error hand-off the producer reads in put().
                telemetry.thread_exception("sink-writer", e)
                with self._lock:
                    self._error = e
                # A failed writer must never leave a torn container on
                # disk, no matter what the producer does next (ISSUE 9
                # satellite): abort every sink HERE, at the chunk
                # boundary the failure landed on.  abort() is
                # idempotent, so the producer's own cleanup racing this
                # is harmless.
                for s in self._sinks:
                    try:
                        s.abort()
                    except BaseException:  # photon-lint: disable=swallowed-exception (cleanup of an already-failed sink; the primary error is already recorded above)
                        pass

    def put(self, lo, hi, margins, preds, labels, ids) -> None:
        err = self._failed()
        if err is not None:
            raise err
        telemetry.gauge("sink.queue_depth", self._q.qsize())
        self._q.put((lo, hi, margins, preds, labels, ids))

    def close(self) -> None:
        self._q.put(self._SENTINEL)
        # Bounded drain (photon-lint eternal-wait): a sink wedged in a
        # hung filesystem write must surface as an actionable error,
        # not pin close() forever.
        self._thread.join(timeout=600.0)
        if self._thread.is_alive():
            raise RuntimeError(
                "score sink writer did not drain within 600s (sink "
                "write wedged); output containers may be incomplete")
        err = self._failed()
        if err is not None:
            raise err


def _fingerprint_arrays(parts, extra: str = "") -> str:
    """blake2b content key over a sequence of arrays (+ a config tag).
    Hashes through the buffer protocol — no ``tobytes`` copy, so the
    transient RSS cost is zero for already-contiguous arrays (the
    bounded-window pipeline must not double-buffer its own inputs)."""
    h = hashlib.blake2b(digest_size=16)
    for a in parts:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(memoryview(a).cast("B"))
    h.update(extra.encode())
    return h.hexdigest()


class StreamingGameScorer:
    """One-pass fused scoring of a ``GameDataset`` with a ``GameModel``.

    ``chunk_rows`` fixes the chunk grid (tail padded).  ``spill_dir``
    (None = chunks are built on the fly each pass, never all resident)
    activates the disk tier: prepared score chunks spill to
    content-keyed ``.npz`` files at plan time — built ONE AT A TIME, so
    the ELL densification never materializes more than a window of
    chunks — and stream back memory-mapped through an LRU
    ``host_max_resident`` window.  ``prefetch_depth`` > 0 runs the
    background disk→host→device prefetch thread either way (without a
    store it overlaps chunk BUILD with device compute).
    """

    def __init__(self, model: GameModel, task: TaskType,
                 chunk_rows: int = 1 << 20,
                 spill_dir: str | None = None,
                 host_max_resident: int = 2,
                 prefetch_depth: int = 2):
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self.model = model
        self.task = task
        self.chunk_rows = int(chunk_rows)
        self.spill_dir = spill_dir
        self.host_max_resident = int(host_max_resident)
        self.prefetch_depth = int(prefetch_depth)
        # Plan memo for repeated score() calls over the SAME dataset
        # object (in-process re-scoring): the plan embeds
        # device tables and — with a spill store — a full content hash
        # of every chunk input, which would otherwise be re-derived per
        # pass.  Identity-keyed (strong ref); callers mutating a
        # dataset in place must use a fresh scorer (or dataset) — the
        # same contract as the training objective's device chunk cache.
        self._plan_memo: tuple | None = None
        self._key_memo: tuple | None = None

    # -- plan ---------------------------------------------------------------

    def _plan(self, dataset: GameDataset):
        """Classify coordinates, resolve entity joins, build device
        tables, and return (specs, tables, build_chunk, key_parts)."""
        from photon_ml_tpu.estimators.game_transformer import (
            _projected_score_table,
            _score_projected_rows,
        )

        n = dataset.n
        R = self.chunk_rows
        specs: list[_CoordSpec] = []
        tables: dict = {}
        builders: dict = {}    # name -> per-chunk host-array builder
        host_parts: list = []  # (model, table, idx, rows) projected REs
        key_parts: list = [dataset.offset_array()]
        key_cfg: list = [f"v{SCORE_CHUNK_VERSION}", f"R{R}"]

        for name, comp in self.model.models.items():
            if isinstance(comp, FixedEffectModel):
                feats = dataset.features[comp.feature_shard]
                w_np = np.asarray(comp.coefficients.means, np.float32)
                if isinstance(feats, np.ndarray):
                    x_all = np.asarray(feats, np.float32)
                    specs.append(_CoordSpec(name, "fixed_dense"))
                    tables[name] = jnp.asarray(
                        w_np[:-1] if comp.intercept else w_np)
                    tables[name + ".base"] = jnp.float32(
                        w_np[-1] if comp.intercept else 0.0)

                    def build_dense(lo, hi, x_all=x_all):
                        x = x_all[lo:hi]
                        if hi - lo < R:
                            x = np.pad(x, ((0, R - (hi - lo)), (0, 0)))
                        return {".x": np.ascontiguousarray(x)}

                    builders[name] = build_dense
                    key_parts.append(x_all)
                    key_cfg.append(f"{name}:dense:{comp.intercept}")
                else:
                    rows = feats if isinstance(feats, SparseRows) else \
                        SparseRows.from_rows(feats)
                    k = max(rows.max_nnz, 1)
                    specs.append(_CoordSpec(name, "fixed_sparse"))
                    tables[name] = jnp.asarray(w_np)
                    tables[name + ".base"] = jnp.float32(
                        w_np[-1] if comp.intercept else 0.0)

                    def build_sparse(lo, hi, rows=rows, k=k):
                        cols, vals = rows[lo:hi].to_ell(
                            row_capacity=k, pad_to=R)
                        return {".cols": cols, ".vals": vals}

                    builders[name] = build_sparse
                    key_parts.extend([rows.indptr, rows.cols, rows.vals])
                    key_cfg.append(f"{name}:sparse:k{k}:{comp.intercept}")
            elif isinstance(comp, RandomEffectModel):
                ids = dataset.entity_ids[comp.entity_key or name]
                idx = comp.grouping.join_ids(ids)
                feats = dataset.features[comp.feature_shard]
                if comp.projection is not None:
                    # Host-side subspace merge-join, chunk by chunk —
                    # folded into the base plane below.
                    rows = feats if isinstance(feats, SparseRows) else \
                        SparseRows.from_rows(feats)
                    table = _projected_score_table(comp)
                    host_parts.append((comp, table, idx, rows))
                    key_parts.extend([rows.indptr, rows.cols, rows.vals,
                                      idx, table[0], table[1]])
                    key_cfg.append(f"{name}:proj")
                    continue
                w_all = np.asarray(comp.all_coefficients(), np.float32)
                E, d_re = w_all.shape
                w_pad = np.vstack([w_all, np.zeros((1, d_re), np.float32)])
                specs.append(_CoordSpec(name, "re"))
                tables[name] = jnp.asarray(w_pad)
                idx32 = np.where(idx < 0, E, idx).astype(np.int32)

                def build_re(lo, hi, feats=feats, idx32=idx32, E=E,
                             d_re=d_re):
                    if isinstance(feats, SparseRows):
                        x = feats[lo:hi].to_dense(d_re)
                    else:
                        x = np.asarray(feats[lo:hi], np.float32)
                    if hi - lo < R:
                        x = np.pad(x, ((0, R - (hi - lo)), (0, 0)))
                    ix = np.full(R, E, np.int32)
                    ix[: hi - lo] = idx32[lo:hi]
                    return {".x": np.ascontiguousarray(x), ".idx": ix}

                builders[name] = build_re
                if isinstance(feats, SparseRows):
                    key_parts.extend([feats.indptr, feats.cols,
                                      feats.vals])
                else:
                    key_parts.append(np.asarray(feats, np.float32))
                key_parts.append(idx32)
                key_cfg.append(f"{name}:re:d{d_re}")
            else:
                raise TypeError(f"unknown component model {type(comp)}")

        offsets = dataset.offset_array()

        def build_chunk(i: int) -> dict:
            lo = i * R
            hi = min(lo + R, n)
            base = np.zeros(R, np.float32)
            base[: hi - lo] = offsets[lo:hi]
            for comp, table, idx, rows in host_parts:
                base[: hi - lo] += _score_projected_rows(
                    comp, table, idx[lo:hi], rows[lo:hi])
            chunk = {"base": base}
            for name, build in builders.items():
                for suffix, arr in build(lo, hi).items():
                    chunk[name + suffix] = arr
            return chunk

        return tuple(specs), tables, build_chunk, (key_parts, key_cfg)

    def _make_program(self, specs):
        mean = self.task.loss.mean

        def run(tables, chunk):
            return _run_chunk(specs, mean, tables, chunk)

        return run

    def _store_key(self, key_parts) -> str:
        """Content key for the spill store, memoized alongside the plan
        (identity on the plan's key_parts): repeated score() calls over
        the same dataset must not re-hash the full content per pass."""
        if self._key_memo is None or self._key_memo[0] is not key_parts:
            parts, cfg = key_parts
            self._key_memo = (
                key_parts,
                "score-" + _fingerprint_arrays(parts, "|".join(cfg)))
        return self._key_memo[1]

    def _make_store(self, n_chunks: int, key_parts, build_chunk):
        from photon_ml_tpu.data.chunk_store import (
            ChunkStore,
            decode_array_chunk,
            encode_array_chunk,
            release_free_heap,
        )

        key = self._store_key(key_parts)
        store = ChunkStore(
            self.spill_dir, key, n_chunks,
            host_max_resident=self.host_max_resident,
            rebuild=build_chunk,
            codec=(encode_array_chunk, decode_array_chunk))
        missing = [i for i in range(n_chunks) if not store.has(i)]
        for i in missing:        # one chunk in flight: bounded ETL RSS
            store.put(i, build_chunk(i))
        if missing:
            release_free_heap()
        logger.info(
            "score chunks: %d spilled to %s (%d built, %d reused; "
            "host window %d)", n_chunks, self.spill_dir, len(missing),
            n_chunks - len(missing), store.host_max_resident)
        return store

    # -- the pass -----------------------------------------------------------

    def score(self, dataset: GameDataset, sinks=(), evaluators=(),
              keep_margins: bool = False) -> dict:
        """One fused pass.  ``sinks``: ``io.score_sink`` writers
        (drained by a background thread).  ``evaluators``:
        ``evaluation.streaming`` adapters (updated in chunk order on
        the main thread).  ``keep_margins`` additionally returns full
        ``margins``/``predictions`` arrays (parity tests / small runs —
        defeats the bounded-memory point at scale)."""
        from photon_ml_tpu.optim.streaming import ChunkPrefetcher

        n = dataset.n
        R = self.chunk_rows
        n_chunks = max(1, -(-n // R))
        if (self._plan_memo is not None
                and self._plan_memo[0] is dataset):
            specs, tables, build_chunk, key_parts = self._plan_memo[1]
        else:
            planned = self._plan(dataset)
            # The dataset object itself anchors the memo (an id() key
            # could be reused by a new dataset after GC); the plan's
            # builders close over its arrays anyway.
            self._plan_memo = (dataset, planned)
            specs, tables, build_chunk, key_parts = planned
        run = self._make_program(specs)

        from photon_ml_tpu.data.chunk_store import probe_spill_dir

        store = None
        # Unwritable spill dir degrades to build-on-the-fly chunks with
        # one warning (ISSUE 9): the disk tier is an optimization here,
        # never a correctness dependency.
        if probe_spill_dir(self.spill_dir) is not None:
            store = self._make_store(n_chunks, key_parts, build_chunk)
            load = store.get
        else:
            load = build_chunk

        labels = dataset.labels
        # Only evaluators read weights; without them the [n] ones array
        # weight_array() synthesizes would be dead resident memory.
        weights = dataset.weight_array() if evaluators else None
        entity_cols = dataset.entity_ids

        margins_out = np.empty(n, np.float32) if keep_margins else None
        preds_out = np.empty(n, np.float32) if keep_margins else None
        writer = _SinkWriter(sinks) if sinks else None
        evaluators = list(evaluators)

        def drain(item) -> None:
            i, m_dev, p_dev = item
            lo = i * R
            hi = min(lo + R, n)
            t0 = time.perf_counter()
            with telemetry.span("chunk_drain", cat="score", chunk=i):
                # Planned D2H harvest spelled explicitly (device_get) so
                # the chunk loop stays clean under
                # guards.no_implicit_transfers.
                m = jax.device_get(m_dev)[: hi - lo]
                p = jax.device_get(p_dev)[: hi - lo]
                lab = labels[lo:hi]
                for ev in evaluators:
                    ev.update(m, p, lab, weights[lo:hi])
                if writer is not None:
                    writer.put(lo, hi, m, p, lab,
                               {k: v[lo:hi]
                                for k, v in entity_cols.items()})
                if keep_margins:
                    margins_out[lo:hi] = m
                    preds_out[lo:hi] = p
            telemetry.observe("score.chunk_drain_s",
                              time.perf_counter() - t0)

        def placed_chunks():
            """Device chunks in order, prefetched (build/disk-read +
            async transfer under compute) when depth > 0."""
            if self.prefetch_depth > 0:
                pf = ChunkPrefetcher(load, jax.device_put,
                                     self.prefetch_depth, store=store)
                pf.start(range(n_chunks))
                try:
                    for i in range(n_chunks):
                        yield pf.next(i)
                finally:
                    pf.close()
            else:
                for i in range(n_chunks):
                    yield jax.device_put(load(i))

        # perf_counter, not time.time: the difference below is DURATION
        # arithmetic (the photon-lint naked-clock rule — wall clock
        # steps under NTP adjustment).
        t0 = time.perf_counter()
        pending: list = []
        try:
            with telemetry.span("score_pass", cat="score",
                                chunks=n_chunks):
                telemetry.count("score.passes")
                for i, buf in enumerate(placed_chunks()):
                    with telemetry.span("chunk_compute", cat="device"):
                        if pending:
                            # Lag-2 dispatch backpressure (the round-8
                            # rule): the previous chunk's margins are
                            # fenced before this chunk dispatches, so
                            # the async queue holds ~two chunks'
                            # buffers, not all K.  D2H copies of
                            # drained chunks keep overlapping
                            # regardless.
                            jax.block_until_ready(pending[-1][1])
                        m, p = run(tables, buf)
                    for out in (m, p):
                        try:
                            out.copy_to_host_async()
                        except AttributeError:  # photon-lint: disable=swallowed-exception (backends without async D2H; drain copies synchronously)
                            pass
                    pending.append((i, m, p))
                    # Live scoring progress in ROWS (ISSUE 10): the
                    # monitor's rolling rate is then rows/s directly.
                    _mon.progress("score", min((i + 1) * R, n), n,
                                  unit="rows")
                    if len(pending) > _INFLIGHT:
                        drain(pending.pop(0))
                for item in pending:
                    drain(item)
                if writer is not None:
                    writer.close()
                    writer = None
                for s in sinks:
                    s.close()
        except BaseException:
            if writer is not None:
                try:
                    writer.close()
                except BaseException:  # photon-lint: disable=swallowed-exception (error-path cleanup; the original pass failure re-raises below)
                    pass
            for s in sinks:
                try:
                    s.abort()
                except BaseException:  # photon-lint: disable=swallowed-exception (error-path cleanup; the original pass failure re-raises below)
                    pass
            raise
        wall_s = time.perf_counter() - t0

        result = {
            "n": int(n),
            "n_chunks": int(n_chunks),
            "chunk_rows": int(R),
            "wall_s": wall_s,
            "rows_per_sec": (n / wall_s) if wall_s > 0 else None,
            "evaluation": {ev.type.value: ev.result()
                           for ev in evaluators},
        }
        if store is not None:
            result["store"] = {
                "loads": store.loads, "hits": store.hits,
                "spills": store.spills,
                "peak_resident": store.peak_resident,
            }
        if keep_margins:
            result["margins"] = margins_out
            result["predictions"] = preds_out
        return result
