"""Static-shape example batches: the TPU-native replacement for RDD[LabeledPoint].

Reference counterpart: ``LabeledPoint`` / per-partition ``Iterable[LabeledPoint]``
(photon-api ``com.linkedin.photon.ml.data`` [expected path, mount unavailable —
see SURVEY.md]).  The reference streams sparse Breeze vectors through a Scala
fold; on TPU we instead materialize a whole (shard of a) dataset as one
static-shape array bundle resident in HBM, so every optimizer iteration is a
handful of fused XLA ops with zero host involvement.

Two layouts:

- ``DenseBatch`` — ``x: [n, d]`` dense features.  Best when d is small
  (a1a: d=124) — margins are one MXU matmul.
- ``SparseBatch`` — padded ELL layout: ``values/col_ids: [n, k]`` where k is
  the per-row nnz capacity (max nnz, possibly bucketed).  ELL keeps shapes
  static (XLA requirement) while storing only k·n entries of a d-wide matrix;
  margins are a gather + row-sum, gradients a segment-sum scatter.  This is
  the TPU answer to Breeze's SparseVector: no CSR row_ptr indirection, which
  would force dynamic slicing inside jit.

Both carry per-example ``labels, weights, offsets`` (offsets implement GAME
coordinate-descent residual passing, reference ``GameDatum.offset``) and a
validity ``mask`` so padding rows contribute zero loss/gradient.

All fields are pytree leaves → batches can be donated, sharded with
``jax.sharding``, and closed over by jit.  ``dim`` is static metadata.
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.colmajor import ColMajorSlice, build_colmajor
from photon_ml_tpu.data.grr import GrrPair, build_grr_pair

Array = jax.Array

# A float32 contraction on a TPU's matrix unit multiplies in bfloat16
# unless told otherwise.  A matrix-vector product alone is lowered to
# multiply + reduce in float32, and so are the vmapped per-entity
# products of narrow blocks; a bucket of wide per-entity blocks (64 rows
# by 391 columns) goes to the matrix unit, where its scores came back
# 1.5e-2 from their float64 values (PERF.md section 6, PR 35).  The
# configuration states float32: the dense contractions say so.
F32 = jax.lax.Precision.HIGHEST


@struct.dataclass
class DenseBatch:
    """Dense feature batch; ``x[i]`` is example i's feature vector."""

    x: Array          # [n, d] float
    labels: Array     # [n] float
    weights: Array    # [n] float
    offsets: Array    # [n] float
    mask: Array       # [n] float, 1.0 = real example, 0.0 = padding

    @property
    def dim(self) -> int:
        return self.x.shape[-1]

    @property
    def n_padded(self) -> int:
        return self.x.shape[-2]

    def margins(self, w: Array) -> Array:
        """x·w + offset, the GLM margin (one MXU matmul)."""
        return jnp.matmul(self.x, w, precision=F32) + self.offsets

    def xt_dot(self, r: Array) -> Array:
        """X^T r — gradient-side contraction (masking folded into r)."""
        return jnp.matmul(self.x.T, r, precision=F32)

    def x_dot(self, v: Array) -> Array:
        """X v — HVP-side contraction."""
        return jnp.matmul(self.x, v, precision=F32)


@struct.dataclass
class SparseBatch:
    """Padded-ELL sparse batch.

    ``col_ids`` padding entries point at column 0 with ``values`` 0.0 so
    gathers stay in-bounds and scatters add zero; correctness never depends
    on the padding target.

    Layout variants for the two contractions (margins X·w, gradient Xᵀr):

    - ``grr`` (``data.grr.GrrPair``, build with ``make_sparse_batch(...,
      grr=True)``): the production TPU path — both directions compiled
      into the gather-route-reduce plan executed by a Mosaic kernel at
      vector speed, with hot columns on the MXU.  ~100× faster than the
      XLA formulations on v5e.
    - ``colmajor`` (``data.colmajor``): transposed-ELL copy making Xᵀr
      a gather+segment-fold instead of a full scatter.  Still pays
      XLA's scalar gather on TPU; useful as the mesh-shardable layout
      and on CPU.
    - neither: plain ELL — margins via XLA gather, Xᵀr via
      ``segment_sum`` scatter.  Fine for small batches and tests.
    """

    values: Array     # [n, k] float
    col_ids: Array    # [n, k] int32
    labels: Array     # [n] float
    weights: Array    # [n] float
    offsets: Array    # [n] float
    mask: Array       # [n] float
    dim: int = struct.field(pytree_node=False)
    colmajor: "ColMajorSlice | None" = None
    grr: "GrrPair | None" = None

    @property
    def n_padded(self) -> int:
        return self.values.shape[-2]

    def margins(self, w: Array) -> Array:
        """Σ_k values[i,k]·w[col_ids[i,k]] + offset."""
        if self.grr is not None:
            return self.grr.dot(w) + self.offsets
        from photon_ml_tpu.ops.kernels import gather_rowsum

        return gather_rowsum(w, self.values, self.col_ids) + self.offsets

    def xt_dot(self, r: Array) -> Array:
        """X^T r — GRR kernel, else transposed-ELL gather, else a
        segment-sum scatter-add into the [dim] gradient."""
        if self.grr is not None:
            return self.grr.t_dot(r)
        if self.colmajor is not None:
            return self.colmajor.xt_dot(r)
        contrib = self.values * r[:, None]            # [n, k]
        return jax.ops.segment_sum(
            contrib.reshape(-1),
            self.col_ids.reshape(-1),
            num_segments=self.dim,
        )

    def x_dot(self, v: Array) -> Array:
        if self.grr is not None:
            return self.grr.dot(v)
        from photon_ml_tpu.ops.kernels import gather_rowsum

        return gather_rowsum(v, self.values, self.col_ids)

    def to_dense(self) -> DenseBatch:
        """Densify (testing / small-dim fast path)."""
        n, k = self.values.shape
        x = jnp.zeros((n, self.dim), self.values.dtype)
        rows = jnp.repeat(jnp.arange(n), k)
        x = x.at[rows, self.col_ids.reshape(-1)].add(self.values.reshape(-1))
        return DenseBatch(
            x=x, labels=self.labels, weights=self.weights,
            offsets=self.offsets, mask=self.mask,
        )


Batch = Union[DenseBatch, SparseBatch]


def make_dense_batch(
    x: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray | None = None,
    offsets: np.ndarray | None = None,
    pad_to: int | None = None,
    dtype=jnp.float32,
) -> DenseBatch:
    """Build a DenseBatch from host arrays, padding rows to ``pad_to``."""
    n, _ = x.shape
    weights = np.ones(n) if weights is None else weights
    offsets = np.zeros(n) if offsets is None else offsets
    mask = np.ones(n)
    if pad_to is not None and pad_to > n:
        pad = pad_to - n
        x = np.pad(x, ((0, pad), (0, 0)))
        labels = np.pad(labels, (0, pad))
        weights = np.pad(weights, (0, pad))
        offsets = np.pad(offsets, (0, pad))
        mask = np.pad(mask, (0, pad))
    return DenseBatch(
        x=jnp.asarray(x, dtype),
        labels=jnp.asarray(labels, dtype),
        weights=jnp.asarray(weights, dtype),
        offsets=jnp.asarray(offsets, dtype),
        mask=jnp.asarray(mask, dtype),
    )


def make_sparse_batch(
    rows: list[tuple[np.ndarray, np.ndarray]],
    dim: int,
    labels: np.ndarray,
    weights: np.ndarray | None = None,
    offsets: np.ndarray | None = None,
    row_capacity: int | None = None,
    pad_to: int | None = None,
    dtype=jnp.float32,
    col_major: bool = False,
    col_capacity: int | None = None,
    grr: bool = False,
    keep_ell: bool = True,
    cache_dir: str | None = None,
) -> SparseBatch:
    """Build a padded-ELL SparseBatch.

    Args:
      rows: per-example ``(col_ids, values)`` numpy pairs.
      dim: feature-space width (static).
      row_capacity: per-row nnz capacity; defaults to the max observed.
      pad_to: pad the example count to this (e.g. a multiple of shard count).
      col_major: also build the transposed-ELL copy so gradients run
        without the full-size scatter (see ``data.colmajor``).
      col_capacity: virtual-row capacity for the transpose (default:
        auto from the column-occupancy distribution).
      grr: compile the GRR plan (``data.grr``) — the fast TPU path for
        both contraction directions; supersedes ``col_major`` when set.
      cache_dir: on-disk GRR plan cache directory (see
        ``photon_ml_tpu.cache``) — a second build of the same data and
        options loads the plan instead of re-deriving it.
      keep_ell: with ``grr``, whether the ELL arrays also go to device.
        The GRR plan serves every contraction, so the device ELL copy
        (8 bytes/nnz of HBM) is only needed by feature statistics /
        normalization and the down-sampled training view; scale runs
        that use neither pass False and the batch stores zero-width
        [n, 0] placeholders instead (SURVEY §7 scale class).
    """
    from photon_ml_tpu.data.sparse_rows import SparseRows

    n = len(rows)
    with telemetry.stage("to_ell", rows=n, dim=dim) as stage:
        if isinstance(rows, SparseRows):
            # Scale path: canonical CSR → ELL in one vectorized scatter.
            # Canonical form already guarantees unique sorted per-row ids
            # (the invariant hessian_diagonal needs).
            k = max(row_capacity or rows.max_nnz, 1)
            n_out = max(pad_to or n, n)
            cols, vals = rows.to_ell(row_capacity=k, pad_to=n_out)
        else:
            k = row_capacity or max((len(c) for c, _ in rows), default=1)
            k = max(k, 1)
            n_out = max(pad_to or n, n)
            vals = np.zeros((n_out, k), np.float32)
            cols = np.zeros((n_out, k), np.int32)
            for i, (c, v) in enumerate(rows):
                if len(c) > k:
                    raise ValueError(
                        f"row {i} nnz {len(c)} exceeds capacity {k}")
                # Duplicate column ids within a row would silently break
                # hessian_diagonal (which squares values elementwise, so
                # duplicates give Σv² instead of (Σv)²); reject them at
                # construction time.
                if len(np.unique(c)) != len(c):
                    raise ValueError(
                        f"row {i} has duplicate column ids; SparseBatch "
                        "requires unique col_ids per row (pre-sum duplicates "
                        "on the host)"
                    )
                vals[i, : len(c)] = v
                cols[i, : len(c)] = c
        weights = np.ones(n) if weights is None else np.asarray(weights)
        offsets = np.zeros(n) if offsets is None else np.asarray(offsets)
        lab = np.zeros(n_out)
        lab[:n] = labels
        wt = np.zeros(n_out)
        wt[:n] = weights
        off = np.zeros(n_out)
        off[:n] = offsets
        mask = np.zeros(n_out)
        mask[:n] = 1.0
        stage.set(k=int(k), padded_rows=int(n_out))
    cm = (
        build_colmajor(cols, vals, dim, capacity=col_capacity)
        if col_major and not grr
        else None
    )
    pair = (build_grr_pair(cols, vals, dim, cache_dir=cache_dir)
            if grr else None)
    if grr and not keep_ell:
        vals = np.zeros((n_out, 0), np.float32)
        cols = np.zeros((n_out, 0), np.int32)
    # Enqueues only: no fence is added for these small arrays (the GRR
    # pair's own fence is ``build_grr_pair``'s ``place_batch``).
    with telemetry.stage(
            "place_batch",
            bytes=sum(int(a.nbytes)
                      for a in (vals, cols, lab, wt, off, mask))):
        return SparseBatch(
            values=jnp.asarray(vals, dtype),
            col_ids=jnp.asarray(cols),
            labels=jnp.asarray(lab, dtype),
            weights=jnp.asarray(wt, dtype),
            offsets=jnp.asarray(off, dtype),
            mask=jnp.asarray(mask, dtype),
            dim=dim,
            colmajor=cm,
            grr=pair,
        )
