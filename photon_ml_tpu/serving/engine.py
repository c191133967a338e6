"""Model-only scoring plan + bucketed fused dispatch (ISSUE 12).

The streaming scorer's plan (``estimators.streaming_scorer._plan``) is
DATASET-bound: its builders close over the pass's arrays.  The serving
tier scores rows that do not exist yet, so this module derives the same
``(_CoordSpec tuple, device tables)`` plan from the MODEL alone and
builds each micro-batch's chunk dict from parsed request rows:

- **The device program is the scorer's** — ``_run_chunk``, jitted at
  module level with the spec tuple and mean function static.  Serving
  adds no second fused program: a bucket batch is just a (small) score
  chunk, and the jit cache (plus the persistent XLA compile cache
  across restarts) is shared with the batch path.
- **Closed shape set**: batches pad to ``ServingConfig.buckets()`` row
  counts, sparse rows densify to ELL at ``ell_row_capacity``, dense
  and random-effect widths come from the model — every steady-state
  dispatch hits a warm compile (guard-pinned by the tests).
- **Random effects** gather per-request coefficient rows from the
  mmap'd ``EntityServeStore`` into a per-batch MINI-table
  ``[R+1, p]`` (row i serves request-row i; the last row is the zero
  fallback shared by unseen entities and padding), so the device never
  holds the [E, p] table — the program's gather-dot is unchanged, only
  the table it gathers from is batch-local.
- **Projected random effects** score host-side per batch (the
  transformer's pre-sorted merge-join table) and fold into ``base``,
  exactly as the streaming scorer folds them per chunk.

``BadRequest`` marks client errors (unknown shard, over-capacity row,
out-of-range column) — the HTTP layer answers 400, never 500.
"""

from __future__ import annotations

import logging

import jax
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.estimators.streaming_scorer import (
    _CoordSpec,
    _run_chunk,
)
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import TaskType
from photon_ml_tpu.serving.entity_store import EntityServeStore

logger = logging.getLogger(__name__)


class BadRequest(ValueError):
    """A malformed scoring request (client error → HTTP 400)."""


def _parse_sparse(feat, dim: int, cap: int, shard: str
                  ) -> tuple[np.ndarray, np.ndarray]:
    """One row's sparse features → (cols int32, vals float32), from
    either ``{"col": val}`` maps or ``[[col, val], ...]`` pairs."""
    if isinstance(feat, dict):
        items = [(int(c), float(v)) for c, v in feat.items()]
    else:
        try:
            items = [(int(c), float(v)) for c, v in feat]
        except (TypeError, ValueError) as e:
            raise BadRequest(
                f"shard '{shard}': sparse features must be a "
                f"col->value map or [[col, value], ...] pairs ({e})")
    if len(items) > cap:
        raise BadRequest(
            f"shard '{shard}': {len(items)} non-zeros exceeds the "
            f"server's ell_row_capacity={cap}; raise the knob or "
            "split the row")
    cols = np.fromiter((c for c, _ in items), np.int32, len(items))
    vals = np.fromiter((v for _, v in items), np.float32, len(items))
    if len(cols) and (cols.min() < 0 or cols.max() >= dim):
        raise BadRequest(
            f"shard '{shard}': column ids must be in [0, {dim})")
    return cols, vals


def _parse_dense(feat, dim: int, shard: str) -> np.ndarray:
    x = np.asarray(feat, np.float32)
    if x.shape != (dim,):
        raise BadRequest(
            f"shard '{shard}': dense features must be a length-{dim} "
            f"vector, got shape {x.shape}")
    return x


class ParsedRow:
    """One validated request row, ready for batch assembly."""

    __slots__ = ("offset", "sparse", "dense", "ids")

    def __init__(self, offset: float, sparse: dict, dense: dict,
                 ids: dict):
        self.offset = offset
        self.sparse = sparse     # shard -> (cols, vals)
        self.dense = dense       # shard -> [d] float32
        self.ids = ids           # entity key -> int


class ScoringEngine:
    """One model version's request-path scorer."""

    def __init__(self, model: GameModel, task: TaskType, *,
                 version: str = "0", ell_row_capacity: int = 64,
                 dense_feature_shards: tuple = (),
                 spill_dir: str | None = None, entity_chunk: int = 4096,
                 host_max_resident: int = 4):
        import jax.numpy as jnp

        from photon_ml_tpu.estimators.game_transformer import (
            _projected_score_table,
        )

        self.model = model
        self.task = task
        self.version = str(version)
        self._mean = task.loss.mean
        self.ell_row_capacity = int(ell_row_capacity)

        specs: list[_CoordSpec] = []
        tables: dict = {}
        # Input schema: shard -> ("sparse", dim) | ("dense", dim);
        # entity key -> required.  Two coordinates sharing a shard must
        # agree on its form (validated below).
        self._shards: dict[str, tuple[str, int]] = {}
        self._entity_keys: list[str] = []
        self._fixed_sparse: list[tuple[str, str]] = []  # (coord, shard)
        self._fixed_dense: list[tuple[str, str]] = []
        self._re: list[tuple[str, str, str, EntityServeStore]] = []
        self._proj: list[tuple[str, RandomEffectModel, tuple, str, str]] \
            = []
        dense_shards = set(dense_feature_shards)

        def declare(shard: str, form: str, dim: int) -> None:
            prev = self._shards.get(shard)
            if prev is not None and prev != (form, dim):
                raise ValueError(
                    f"feature shard '{shard}' is used as {prev} and as "
                    f"({form}, {dim}) by different coordinates; serving "
                    "needs one form per shard")
            self._shards[shard] = (form, dim)

        for name, comp in model.models.items():
            if isinstance(comp, FixedEffectModel):
                w = np.asarray(comp.coefficients.means, np.float32)
                dim = len(w) - (1 if comp.intercept else 0)
                if comp.feature_shard in dense_shards:
                    specs.append(_CoordSpec(name, "fixed_dense"))
                    tables[name] = jnp.asarray(
                        w[:-1] if comp.intercept else w)
                    tables[name + ".base"] = jnp.float32(
                        w[-1] if comp.intercept else 0.0)
                    declare(comp.feature_shard, "dense", dim)
                    self._fixed_dense.append((name, comp.feature_shard))
                else:
                    specs.append(_CoordSpec(name, "fixed_sparse"))
                    tables[name] = jnp.asarray(w)
                    tables[name + ".base"] = jnp.float32(
                        w[-1] if comp.intercept else 0.0)
                    declare(comp.feature_shard, "sparse", dim)
                    self._fixed_sparse.append((name, comp.feature_shard))
            elif isinstance(comp, RandomEffectModel):
                key = comp.entity_key or name
                self._entity_keys.append(key)
                if comp.projection is not None:
                    table = _projected_score_table(comp)
                    declare(comp.feature_shard, "sparse",
                            comp.projection.global_dim)
                    self._proj.append((name, comp, table,
                                       comp.feature_shard, key))
                    continue
                store = EntityServeStore.build(
                    name, comp, spill_dir, entity_chunk=entity_chunk,
                    host_max_resident=host_max_resident)
                specs.append(_CoordSpec(name, "re"))
                declare(comp.feature_shard, "dense", store.dim)
                self._re.append((name, comp.feature_shard, key, store))
            else:
                raise TypeError(f"unknown component model {type(comp)}")

        self.specs = tuple(specs)
        self._tables = tables          # device-resident, model-constant
        self.warmed_buckets: list[int] = []

    # -- request parsing ----------------------------------------------------

    def parse_row(self, row) -> ParsedRow:
        if not isinstance(row, dict):
            raise BadRequest("each row must be a JSON object")
        feats = row.get("features")
        if not isinstance(feats, dict):
            raise BadRequest("each row needs a 'features' object "
                             "(shard -> features)")
        unknown = set(feats) - set(self._shards)
        if unknown:
            raise BadRequest(
                f"unknown feature shard(s) {sorted(unknown)}; the "
                f"model serves {sorted(self._shards)}")
        sparse: dict = {}
        dense: dict = {}
        for shard, (form, dim) in self._shards.items():
            if shard not in feats:
                raise BadRequest(f"row is missing feature shard "
                                 f"'{shard}'")
            if form == "sparse":
                sparse[shard] = _parse_sparse(
                    feats[shard], dim, self.ell_row_capacity, shard)
            else:
                dense[shard] = _parse_dense(feats[shard], dim, shard)
        raw_ids = row.get("ids") or {}
        ids: dict = {}
        for key in self._entity_keys:
            if key not in raw_ids:
                raise BadRequest(f"row is missing entity id '{key}'")
            try:
                ids[key] = int(raw_ids[key])
            except (TypeError, ValueError):
                raise BadRequest(f"entity id '{key}' must be an "
                                 "integer")
        try:
            offset = float(row.get("offset", 0.0))
        except (TypeError, ValueError):
            raise BadRequest("'offset' must be a number")
        return ParsedRow(offset, sparse, dense, ids)

    def parse_rows(self, rows) -> list[ParsedRow]:
        if not isinstance(rows, list) or not rows:
            raise BadRequest("'rows' must be a non-empty list")
        return [self.parse_row(r) for r in rows]

    # -- batch assembly + dispatch ------------------------------------------

    def _zero_rows(self, n: int) -> list[ParsedRow]:
        """Synthetic all-zeros rows (bucket warm-up)."""
        sparse = {s: (np.zeros(0, np.int32), np.zeros(0, np.float32))
                  for s, (f, _) in self._shards.items() if f == "sparse"}
        dense = {s: np.zeros(d, np.float32)
                 for s, (f, d) in self._shards.items() if f == "dense"}
        ids = {k: -1 for k in self._entity_keys}
        return [ParsedRow(0.0, dict(sparse), dict(dense), dict(ids))
                for _ in range(n)]

    def _build_chunk(self, rows: list[ParsedRow], R: int,
                     timings: dict | None = None
                     ) -> tuple[dict, dict, np.ndarray]:
        """(chunk arrays, per-batch tables, degraded [n] bool) for
        ``rows`` padded to ``R`` — all host numpy; placement is the
        caller's explicit ``device_put``.  ``degraded[i]`` marks row i
        served fixed-effect-only fallback by an entity store
        (ISSUE 13) — per row, so co-batched healthy requests stay
        unmarked.  ``timings`` (ISSUE 14): accumulates the
        entity-store lookup seconds under ``"store_lookup"`` so the
        batch trace can split lookup out of assembly."""
        import time as _time

        n = len(rows)
        k = self.ell_row_capacity
        base = np.zeros(R, np.float32)
        for i, r in enumerate(rows):
            base[i] = r.offset
        chunk: dict = {}
        # Shared per-shard staging (coordinates reusing a shard reuse
        # the staged arrays instead of re-padding).
        ell: dict = {}
        for shard, (form, dim) in self._shards.items():
            if form == "sparse":
                cols = np.zeros((R, k), np.int32)
                vals = np.zeros((R, k), np.float32)
                for i, r in enumerate(rows):
                    c, v = r.sparse[shard]
                    cols[i, : len(c)] = c
                    vals[i, : len(v)] = v
                ell[shard] = (cols, vals)
            else:
                x = np.zeros((R, dim), np.float32)
                for i, r in enumerate(rows):
                    x[i] = r.dense[shard]
                ell[shard] = x
        for name, shard in self._fixed_sparse:
            chunk[name + ".cols"], chunk[name + ".vals"] = ell[shard]
        for name, shard in self._fixed_dense:
            chunk[name + ".x"] = ell[shard]
        batch_tables: dict = {}
        degraded = np.zeros(n, bool)
        for name, shard, key, store in self._re:
            ids = np.fromiter((r.ids[key] for r in rows), np.int64, n)
            if timings is None:
                w_rows, _hit, deg = store.lookup(ids)
            else:
                t_l = _time.perf_counter()
                w_rows, _hit, deg = store.lookup(ids)
                timings["store_lookup"] = (
                    timings.get("store_lookup", 0.0)
                    + _time.perf_counter() - t_l)
            degraded |= deg
            # Mini-table: row i serves request-row i; row R is the
            # shared zero fallback (unseen entities + padding) — the
            # batch path's unseen-entity semantics, bitwise.
            mt = np.zeros((R + 1, store.dim), np.float32)
            mt[:n] = w_rows
            idx = np.full(R, R, np.int32)
            idx[:n] = np.arange(n, dtype=np.int32)
            chunk[name + ".x"] = ell[shard]
            chunk[name + ".idx"] = idx
            batch_tables[name] = mt
        for name, comp, table, shard, key in self._proj:
            cols, vals = zip(*(r.sparse[shard] for r in rows)) \
                if n else ((), ())
            lens = np.fromiter((len(c) for c in cols), np.int64, n)
            indptr = np.zeros(n + 1, np.int64)
            np.cumsum(lens, out=indptr[1:])
            srows = SparseRows.from_flat(
                indptr,
                (np.concatenate(cols) if n else
                 np.zeros(0, np.int64)).astype(np.int64),
                np.concatenate(vals).astype(np.float32) if n
                else np.zeros(0, np.float32))
            ids = np.fromiter((r.ids[key] for r in rows), np.int64, n)
            idx = comp.grouping.join_ids(ids)
            from photon_ml_tpu.estimators.game_transformer import (
                _score_projected_rows,
            )

            base[:n] += _score_projected_rows(comp, table, idx, srows)
        chunk["base"] = base
        return chunk, batch_tables, degraded

    def score_batch(self, rows: list[ParsedRow], bucket: int,
                    trace=None
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Score ``rows`` padded to ``bucket`` → (margins [n],
        predictions [n], degraded [n] bool) as host numpy.  One fused
        device dispatch; ``degraded`` marks the fixed-effect-only
        fallback rows from an unreadable entity-store chunk
        (ISSUE 13).

        ``trace`` (ISSUE 14): the shared ``BatchTrace`` — stage
        durations stamp onto it (``assemble`` = chunk build minus
        lookups, ``store_lookup`` = entity-store reads, ``dispatch`` =
        H2D placement + program enqueue, ``d2h`` = block-until-done +
        harvest; the async dispatch means device compute time lands in
        ``d2h``).  None keeps the pre-tracing path: no timestamps
        taken."""
        import time as _time

        from photon_ml_tpu.reliability import faults

        n = len(rows)
        if n > bucket:
            raise ValueError(f"{n} rows > bucket {bucket}")
        timings = None if trace is None else {}
        t_a = 0.0 if trace is None else _time.perf_counter()
        chunk, batch_tables, degraded = self._build_chunk(
            rows, bucket, timings)
        if trace is not None:
            lookup_s = timings.get("store_lookup", 0.0)
            trace.stamp("store_lookup", lookup_s)
            trace.stamp("assemble",
                        _time.perf_counter() - t_a - lookup_s)
        # The engine-dispatch fault seam: a wedged/failing device
        # dispatch is injectable here (the batcher maps the error to
        # the whole batch's slots — an answered 500, never a hang).
        faults.fire("serve.dispatch", bucket=bucket)
        # Explicit placement + harvest (the no_implicit_transfers
        # contract): the batch chunk and the RE mini-tables go up in
        # one planned device_put; margins/preds come back in one
        # device_get.
        t_d = 0.0 if trace is None else _time.perf_counter()
        buf = jax.device_put(chunk)
        tables = self._tables
        if batch_tables:
            tables = {**tables, **jax.device_put(batch_tables)}
        m_dev, p_dev = _run_chunk(self.specs, self._mean, tables, buf)
        t_h = 0.0 if trace is None else _time.perf_counter()
        if trace is not None:
            trace.stamp("dispatch", t_h - t_d)
        m = np.asarray(jax.device_get(m_dev)[:n])
        p = np.asarray(jax.device_get(p_dev)[:n])
        if trace is not None:
            trace.stamp("d2h", _time.perf_counter() - t_h)
        return m, p, degraded

    def warm(self, buckets: list[int]) -> dict:
        """Compile (or warm-load from the persistent XLA cache) every
        bucket shape so the first request pays zero compiles."""
        import time

        t0 = time.perf_counter()
        for b in sorted(buckets):
            self.score_batch(self._zero_rows(1), b)
            self.warmed_buckets.append(int(b))
        warm_s = time.perf_counter() - t0
        telemetry.observe("serve.warm_s", warm_s)
        logger.info("scoring engine warmed %d bucket(s) %s in %.2fs",
                    len(self.warmed_buckets), self.warmed_buckets,
                    warm_s)
        return {"buckets": list(self.warmed_buckets),
                "warm_s": round(warm_s, 3)}

    # -- introspection / retirement -----------------------------------------

    def describe(self) -> dict:
        return {
            "version": self.version,
            "coordinates": {s.name: s.kind for s in self.specs}
            | {name: "re_projected" for name, *_ in self._proj},
            "shards": {s: {"form": f, "dim": d}
                       for s, (f, d) in self._shards.items()},
            "entity_keys": list(self._entity_keys),
            "ell_row_capacity": self.ell_row_capacity,
            "buckets": list(self.warmed_buckets),
            "entity_stores": [store.stats()
                              for *_x, store in self._re],
        }

    def close(self) -> None:
        """Retire this engine (after in-flight batches drained): drop
        the entity stores' decoded windows."""
        for *_x, store in self._re:
            store.close()


def dataset_rows(dataset, lo: int, hi: int) -> list[dict]:
    """``GameDataset`` rows [lo, hi) → request-row JSON objects (the
    ``/v1/score`` wire shape).  Test/client helper: the parity
    suites and open-loop clients replay real dataset rows
    against the server."""
    offsets = dataset.offset_array()
    sparse = {s: (f if isinstance(f, SparseRows)
                  else SparseRows.from_rows(f))
              for s, f in dataset.features.items()
              if not isinstance(f, np.ndarray)}
    rows = []
    for i in range(lo, hi):
        feats: dict = {}
        for shard, f in dataset.features.items():
            if isinstance(f, np.ndarray):
                feats[shard] = [float(v) for v in f[i]]
            else:
                f = sparse[shard]
                s0, s1 = int(f.indptr[i]), int(f.indptr[i + 1])
                feats[shard] = [[int(c), float(v)]
                                for c, v in zip(f.cols[s0:s1],
                                                f.vals[s0:s1])]
        rows.append({
            "features": feats,
            "ids": {k: int(v[i])
                    for k, v in dataset.entity_ids.items()},
            "offset": float(offsets[i]),
        })
    return rows
