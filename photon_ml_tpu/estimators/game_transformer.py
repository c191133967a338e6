"""GameTransformer: batch scoring of (new) data with a GameModel.

Reference counterpart: ``GameTransformer``
(photon-api ``com.linkedin.photon.ml.transformers.GameTransformer``
[expected path, mount unavailable — see SURVEY.md §2.6/§3.2]).

The reference scores per coordinate — fixed effect by broadcasting
coefficients over the data, random effects by joining data with the
per-entity coefficient RDD — and sums ``CoordinateDataScores``.  Here:

- fixed effect: one matmul (dense shard) or ELL gather-dot (sparse),
- random effect: host-side entity-id → trained-entity-index resolution
  (the "join"), then a device gather of coefficient rows + dot.
  Entities unseen at training time score 0, the reference's semantics.

The summed scores are raw margins (``ModelDataScores``); callers apply
the task's mean function for probability-space outputs.

``transform`` walks the dataset once PER COORDINATE with host float64
accumulation — right for validation-sized data between CD sweeps.  The
serving-scale path is ``transform_streamed`` /
``estimators.streaming_scorer``: one pass in fixed-shape chunks where a
single fused device program scores every coordinate at once (ISSUE 4).
The per-coordinate helpers here are shared by both paths.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.models.game import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import TaskType

Array = jax.Array


# Below this many rows the host numpy pass beats device dispatch +
# transfer; above it, scoring streams chunks through the accelerator
# (round-4 verdict: training rode the device, scoring 10⁸ rows must not
# stay on host float64).  Applies to the fixed-effect sparse path AND
# (ISSUE 4 satellite) the non-projected random-effect gather-dot.
_DEVICE_SCORE_MIN_ROWS = 200_000
_DEVICE_SCORE_CHUNK = 2_000_000


@functools.lru_cache(maxsize=None)
def _jit_scorer(fn):
    """Memoized jit wrapper: a per-call ``jax.jit(gather_rowsum)``
    gave every ``_device_score_sparse`` invocation a fresh executable
    cache, re-tracing and recompiling the identical program once per
    scoring call (photon-lint jit-in-function; the PR-2 recompile
    hazard, found at lint introduction).  Keyed on the function object
    so the production path reuses ONE compiled wrapper while a
    monkeypatched spy (tests) transparently gets its own."""
    return jax.jit(fn)


def _device_score_sparse(rows, w_np: np.ndarray) -> np.ndarray:
    """Chunked device X·w over SparseRows: equal-shape ELL chunks (the
    tail is padded, so ONE compile serves every chunk), with at most
    two chunks in flight — chunk i's output is consumed before chunk
    i+2 dispatches, bounding device residency to two chunk buffers
    (unbounded dispatch-ahead would queue the whole dataset's ELL on
    device, defeating the chunking).

    The chunk grid is sized to min(n, _DEVICE_SCORE_CHUNK) rounded up
    to an 8192-row tile (advisor finding: padding every input to the
    fixed 2M grid made a 250k-row input pay ~8× wasted
    gather/rowsum/transfer); one compile still serves every chunk of a
    given input."""
    from photon_ml_tpu.ops import kernels

    n = len(rows)
    k = max(rows.max_nnz, 1)
    grid = -(-min(n, _DEVICE_SCORE_CHUNK) // 8192) * 8192
    w_dev = jnp.asarray(w_np, jnp.float32)
    score = _jit_scorer(kernels.gather_rowsum)
    outs = []
    pending: list = []
    for lo in range(0, n, grid):
        hi = min(lo + grid, n)
        cols, vals = rows[lo:hi].to_ell(row_capacity=k,
                                        pad_to=grid)
        pending.append(
            (score(w_dev, jnp.asarray(vals), jnp.asarray(cols)), hi - lo))
        if len(pending) >= 2:
            out, m = pending.pop(0)
            outs.append(np.asarray(out)[:m])
    for out, m in pending:
        outs.append(np.asarray(out)[:m])
    return np.concatenate(outs) if outs else np.zeros(0, np.float32)


@jax.jit
def _re_gather_dot(W_pad: Array, x: Array, idx: Array) -> Array:
    """``out[i] = x[i] · W_pad[idx[i]]`` — the random-effect
    coefficient-row gather-dot (the scoring-side "join" contraction;
    ``idx`` points unseen entities at the zero padding row)."""
    return jnp.sum(x * W_pad[idx], axis=-1)


def _device_score_re(feats, w_pad: np.ndarray,
                     idx: np.ndarray) -> np.ndarray:
    """Chunked device gather+dot for the non-projected random effect
    (ISSUE 4 satellite: the host ``np.einsum`` did this regardless of
    size).  Same two-in-flight chunk discipline as
    ``_device_score_sparse``; ``feats`` is a dense [n, d_re] array or
    ``SparseRows`` (densified per chunk — RE shards are narrow)."""
    from photon_ml_tpu.data.sparse_rows import SparseRows

    n = len(idx)
    d_re = w_pad.shape[1]
    grid = -(-min(n, _DEVICE_SCORE_CHUNK) // 8192) * 8192
    W_dev = jnp.asarray(w_pad, jnp.float32)
    pad_row = w_pad.shape[0] - 1
    outs = []
    pending: list = []
    for lo in range(0, n, grid):
        hi = min(lo + grid, n)
        if isinstance(feats, SparseRows):
            x = feats[lo:hi].to_dense(d_re)
        else:
            x = np.asarray(feats[lo:hi], np.float32)
        if hi - lo < grid:
            x = np.pad(x, ((0, grid - (hi - lo)), (0, 0)))
        ix = np.full(grid, pad_row, np.int32)
        ix[: hi - lo] = np.where(idx[lo:hi] < 0, pad_row,
                                 idx[lo:hi]).astype(np.int32)
        pending.append(
            (_re_gather_dot(W_dev, jnp.asarray(x), jnp.asarray(ix)),
             hi - lo))
        if len(pending) >= 2:
            out, m = pending.pop(0)
            outs.append(np.asarray(out)[:m])
    for out, m in pending:
        outs.append(np.asarray(out)[:m])
    return (np.concatenate(outs) if outs
            else np.zeros(0, np.float32))


def _score_fixed(model: FixedEffectModel, dataset: GameDataset) -> np.ndarray:
    feats = dataset.features[model.feature_shard]
    w_np = np.asarray(model.coefficients.means)
    if isinstance(feats, np.ndarray):
        x = np.asarray(feats, np.float32)
        if model.intercept:
            x = np.concatenate([x, np.ones((len(x), 1), np.float32)], 1)
        return np.asarray(jnp.asarray(x) @ jnp.asarray(w_np))
    # Sparse rows: intercept is the last coefficient.  (GameDataset
    # normalizes legacy list rows to SparseRows at construction, so
    # this is the only sparse path.)  Large inputs stream through the
    # accelerator; small ones stay on the host numpy pass.
    base = w_np[-1] if model.intercept else 0.0
    from photon_ml_tpu.data.sparse_rows import SparseRows

    rows = feats if isinstance(feats, SparseRows) else \
        SparseRows.from_rows(feats)
    if (len(rows) >= _DEVICE_SCORE_MIN_ROWS
            and jax.default_backend() != "cpu"):
        return (_device_score_sparse(rows, w_np).astype(np.float64)
                + np.float32(base))
    return rows.dot_dense(w_np.astype(np.float64)) + np.float32(base)


def _projected_score_table(
    model: RandomEffectModel) -> tuple[np.ndarray, np.ndarray]:
    """Projected model → sorted ``(entity_row·G + global_col) → value``
    map: the model side of the scoring merge-join, computed ONCE and
    reused per chunk (the streaming scorer joins against it chunk by
    chunk; ``transform`` in one shot)."""
    G = np.int64(model.projection.global_dim)
    keys_parts, vals_parts = [], []
    ent_row_of = model.grouping.entity_row_map()
    for b, blk in enumerate(model.coefficient_blocks):
        fids = model.projection.feature_ids[b]
        blk = np.asarray(blk)
        rr, cc = np.nonzero(fids >= 0)
        if not len(rr):
            continue
        erow = ent_row_of[b, rr]
        keys_parts.append(erow * G + fids[rr, cc])
        vals_parts.append(blk[rr, cc].astype(np.float64))
    if not keys_parts:
        return np.zeros(0, np.int64), np.zeros(0, np.float64)
    keys = np.concatenate(keys_parts)
    vals = np.concatenate(vals_parts)
    # a bucket's keys are already ascending where its entities' features
    # are (``build_subspace_projection`` lays them out so): a stable
    # sort merges those runs, and still sorts any other layout
    order = np.argsort(keys, kind="stable")
    return keys[order], vals[order]


def _score_projected_rows(model: RandomEffectModel, table, idx, rows
                          ) -> np.ndarray:
    """Projected-model scores for one row range: merge-join of the
    rows' (entity row, global col) keys against the pre-sorted model
    table — all vectorized (no per-example Python).  ``idx`` is the
    rows' global entity index (−1 unseen), ``table`` from
    ``_projected_score_table``."""
    from photon_ml_tpu.game.dataset import sorted_key_join

    ks, vs = table
    n = len(rows)
    if ks.size == 0:
        return np.zeros(n, np.float32)
    G = np.int64(model.projection.global_dim)
    # One key per stored entry whose example's entity trained AND whose
    # column is inside the trained global space — out-of-space ids
    # would alias into the next entity's key range.
    row_of = rows.row_of()
    erow_nnz = idx[row_of]
    dsel = (erow_nnz >= 0) & (rows.cols.astype(np.int64) < G)
    key_d = erow_nnz[dsel] * G + rows.cols[dsel].astype(np.int64)
    w_at, hit = sorted_key_join(ks, vs, key_d, presorted=True)
    contrib = np.zeros(rows.nnz, np.float64)
    contrib[dsel] = np.where(hit, w_at, 0.0) * rows.vals[dsel]
    cs = np.zeros(rows.nnz + 1, np.float64)
    np.cumsum(contrib, out=cs[1:])
    return (cs[rows.indptr[1:]] - cs[rows.indptr[:-1]]).astype(np.float32)


def _score_random(model: RandomEffectModel, entity_ids: np.ndarray,
                  dataset: GameDataset) -> np.ndarray:
    from photon_ml_tpu.data.sparse_rows import SparseRows

    n = dataset.n
    idx = model.grouping.join_ids(entity_ids)

    if model.projection is None:
        feats = dataset.features[model.feature_shard]
        w_all = np.asarray(model.all_coefficients())   # [E, d_re]
        w_pad = np.vstack([w_all, np.zeros((1, w_all.shape[1]), w_all.dtype)])
        if (n >= _DEVICE_SCORE_MIN_ROWS
                and jax.default_backend() != "cpu"):
            # Large inputs ride the accelerator (gather+dot chunks) —
            # the sparse fixed-effect discipline, applied to the RE
            # coefficient-row gather (ISSUE 4 satellite).
            return _device_score_re(feats, w_pad, idx)
        x = np.asarray(feats, np.float32)
        gathered = w_pad[idx]                           # -1 → zero row
        return np.einsum("nd,nd->n", x, gathered).astype(np.float32)

    # Projected model: score in each entity's local subspace via a
    # sorted merge-join of (entity row, global col) keys — data side
    # from the example features, model side from each entity's
    # subspace.
    feats = dataset.features[model.feature_shard]
    rows = SparseRows.from_rows(feats)
    table = _projected_score_table(model)
    return _score_projected_rows(model, table, idx, rows)


@dataclasses.dataclass
class GameTransformer:
    """Score a GameDataset with a GameModel (margins per example)."""

    model: GameModel
    task: TaskType

    def transform(self, dataset: GameDataset) -> np.ndarray:
        """Summed raw scores [n] (+ dataset offsets, reference semantics)."""
        from photon_ml_tpu import telemetry

        total = dataset.offset_array().astype(np.float64).copy()
        with telemetry.stage("transform", rows=int(dataset.n)):
            for name, comp in self.model.models.items():
                # One span per coordinate pass: the resident path walks
                # the dataset once PER COORDINATE — the report shows
                # which coordinate's pass dominates.
                with telemetry.stage("score_coordinate", coordinate=name):
                    if isinstance(comp, FixedEffectModel):
                        total += _score_fixed(comp, dataset)
                    elif isinstance(comp, RandomEffectModel):
                        ids = dataset.entity_ids[comp.entity_key or name]
                        total += _score_random(comp, ids, dataset)
                    else:
                        raise TypeError(
                            f"unknown component model {type(comp)}")
        return total.astype(np.float32)

    def transform_streamed(self, dataset: GameDataset,
                           score_chunk_rows: int = 1 << 20,
                           spill_dir: str | None = None,
                           host_max_resident: int = 2,
                           prefetch_depth: int = 2) -> np.ndarray:
        """Margins via the one-pass fused chunk pipeline
        (``estimators.streaming_scorer``) — identical to ``transform``
        up to float-summation order, with memory bounded by the chunk
        window instead of per-coordinate full passes."""
        from photon_ml_tpu.estimators.streaming_scorer import (
            StreamingGameScorer,
        )

        scorer = StreamingGameScorer(
            model=self.model, task=self.task,
            chunk_rows=score_chunk_rows, spill_dir=spill_dir,
            host_max_resident=host_max_resident,
            prefetch_depth=prefetch_depth)
        return scorer.score(dataset, keep_margins=True)["margins"]

    def transform_mean(self, dataset: GameDataset) -> np.ndarray:
        """Mean-space predictions (sigmoid/identity/exp of margins)."""
        margins = self.transform(dataset)
        return np.asarray(self.task.loss.mean(jnp.asarray(margins)))
