"""GAME coordinates: the per-coordinate train/score units.

Reference counterparts: ``Coordinate``, ``FixedEffectCoordinate``,
``RandomEffectCoordinate`` (photon-api
``com.linkedin.photon.ml.algorithm`` [expected paths, mount unavailable —
see SURVEY.md §2.3]).

The reference contract carries over exactly — ``train(offsets, warm
start) → model`` and ``score(model) → per-example scores`` — but the
execution model flips:

- ``FixedEffectCoordinate``: the reference runs
  ``DistributedOptimizationProblem`` (broadcast + treeAggregate per
  L-BFGS iteration).  Here the SAME ``OptimizationProblem`` runs over
  either a local batch or a mesh-sharded batch wrapped in
  ``DistributedGLMObjective`` — one jitted solve either way.
- ``RandomEffectCoordinate``: the reference's
  ``RDD[(REId, LocalDataset)].mapValues(solve per entity)`` — thousands
  of sequential JVM L-BFGS loops per partition — becomes ONE
  ``vmap``ped solve per size bucket: every entity in a bucket optimizes
  simultaneously on the VPU/MXU, each converging by its own criterion
  (masked while_loop).  Entity blocks are built once by the host ETL
  (``EntityGrouping``); per-CD-iteration offsets move between example
  space and block space by static-index gather/scatter on device.

- ``StreamedRandomEffectCoordinate`` (round 10): the same vmapped
  per-bucket solve driven chunk-by-chunk through the out-of-core chunk
  store + prefetch pipeline, with converged-entity retirement between
  CD sweeps — entity count bounded by DISK and the host window, not by
  residency (see the class docstring).

Scores are raw dot products x·w (no offset, no link), summable across
coordinates — the reference's ``CoordinateDataScores`` convention.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import convergence as _conv
from photon_ml_tpu.telemetry import device as _device
from photon_ml_tpu.telemetry import monitor as _mon
from photon_ml_tpu.data.batch import F32, Batch, DenseBatch
from photon_ml_tpu.game.dataset import (
    EntityGrouping,
    GameDataset,
    group_by_entity,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import FixedEffectModel, RandomEffectModel
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import OptimizationProblem, OptimizerConfig
from photon_ml_tpu.optim.lbfgs import lbfgs_solve
from photon_ml_tpu.optim.tron import tron_solve
from photon_ml_tpu.parallel.distributed_objective import DistributedGLMObjective

logger = logging.getLogger(__name__)

Array = jax.Array


# ---------------------------------------------------------------------------
# Module-level jitted solves.  Everything data-like (batch, objective with
# its traced reg/norm arrays, offsets, warm starts) is a TRACED argument;
# only the optimizer type and config are static.  Two consequences, both
# verdict findings from round 2:
#   * grid/tuning points that differ only in reg weight λ hit the SAME
#     compiled executable (λ lives in RegularizationContext leaves);
#   * the batch is never closed over as a jit constant — a constant batch
#     would be baked into the HLO and shipped through the compiler, which
#     at production sizes means gigabytes through the compile path.
# ---------------------------------------------------------------------------


def _pad_offsets(offsets: Array, n_padded: int) -> Array:
    """Example-space offsets [n] → batch row space [n_padded] (padding
    rows are masked, so zeros are exact)."""
    if offsets.shape[0] == n_padded:
        return offsets
    return jnp.pad(offsets, (0, n_padded - offsets.shape[0]))


def _apply_training_view(batch, offsets: Array, train_idx, train_weights):
    """Offsets installed; optionally the down-sampled row view."""
    offsets = _pad_offsets(offsets, batch.n_padded)
    if train_idx is None:
        return batch.replace(offsets=offsets)
    from photon_ml_tpu.data.batch import SparseBatch

    base = batch
    if isinstance(base, SparseBatch) and (
        base.colmajor is not None or base.grr is not None
    ):
        # The transposed-ELL / GRR plans index *all* rows; subsetting
        # their layout arrays by example ids would silently corrupt
        # X^T r.  Drop them — the subsetted batch falls back to the ELL
        # paths (down-sampled solves are smaller anyway).
        base = base.replace(colmajor=None, grr=None)
    sub = jax.tree.map(lambda a: a[train_idx], base)
    return sub.replace(offsets=offsets[train_idx], weights=train_weights)


def _jit_solve(fn, donate_argnums):
    """(plain, warm-start-donating) jit pair for a solve entry.

    Donation (SURVEY §5.2 rebuild guidance): the warm-start
    coefficients are the one solve input shaped like a solve output, so
    XLA can write the new coefficients into the old buffer — for
    random effects that is the full [E_b, cap, p]-adjacent coefficient
    blocks, the dominant recurring allocation of a CD sweep.
    Coordinate descent rebinds ``coefs[name]`` to the result
    immediately after each call, so the donated buffer is dead there;
    direct ``train()`` callers (tests, notebooks) may reuse their
    arrays, so the plain variant stays the default — donation is
    opt-in via ``donate_warm_start``.
    """
    return (
        # photon-lint: disable=jit-in-function (module-import-time factory)
        jax.jit(fn, static_argnums=(0, 1, 2)),
        # photon-lint: disable=jit-in-function (module-import-time factory)
        jax.jit(fn, static_argnums=(0, 1, 2),
                donate_argnums=donate_argnums))


def _fixed_train_local_impl(optimizer, config, has_l1, objective, batch,
                            offsets, train_idx, train_weights, w0):
    problem = OptimizationProblem(
        objective=objective, optimizer=optimizer, config=config
    )
    view = _apply_training_view(batch, offsets, train_idx, train_weights)
    return problem.run(view, w0, has_l1=has_l1)


_fixed_train_local, _fixed_train_local_donating = _jit_solve(
    _fixed_train_local_impl, donate_argnums=(8,))  # w0


def _fixed_train_distributed_impl(optimizer, config, has_l1, dist_obj, batch,
                                  offsets, train_idx, train_weights, w0):
    from photon_ml_tpu.optim.base import OptimizerType

    view = _apply_training_view(batch, offsets, train_idx, train_weights)
    vg = lambda w: dist_obj.value_and_gradient(w, view)
    if optimizer == OptimizerType.TRON:
        if has_l1:
            raise ValueError(
                "TRON requires a smooth objective; use LBFGS (OWL-QN) "
                "for L1/elastic-net problems"
            )
        hvp = lambda w, v: dist_obj.hessian_vector(w, v, view)
        return tron_solve(vg, hvp, w0, config)
    problem = OptimizationProblem(
        objective=dist_obj.objective, optimizer=optimizer, config=config
    )
    l1 = problem._l1_vector(w0.shape[-1]) if has_l1 else None
    return lbfgs_solve(vg, w0, config, l1_weight=l1)


_fixed_train_distributed, _fixed_train_distributed_donating = _jit_solve(
    _fixed_train_distributed_impl, donate_argnums=(8,))  # w0


def _lane_vg(objective, view):
    """Per-lane smooth objective for the swept solvers: the lane's L2
    weight rides as the lane context (a traced [L] leaf row), so one
    compiled program covers any λ grid."""
    def vg(w, l2):
        obj = objective.replace(reg=objective.reg.replace(l2_weight=l2))
        return obj.value_and_gradient(w, view)
    return vg


@partial(jax.jit, static_argnums=(0, 1))
def _fixed_train_swept(config, use_map, objective, batch, offsets,
                       train_idx, train_weights, W0, l2s, l1v):
    """Batched λ-sweep fixed-effect solve: W0 [L, d] lanes against ONE
    shared training view — the whole regularization grid in a single
    masked-lane program (``optim.lbfgs.lbfgs_solve_swept``).
    ``use_map`` (static) lane-loops via ``lax.map`` when the batch
    carries a GRR plan (the Pallas kernel has no batching rule)."""
    from photon_ml_tpu.optim.lbfgs import lbfgs_solve_swept

    view = _apply_training_view(batch, offsets, train_idx, train_weights)
    return lbfgs_solve_swept(_lane_vg(objective, view), W0, l2s, config,
                             l1_weights=l1v, use_map=use_map)


@partial(jax.jit, static_argnums=(0,))
def _fixed_train_swept_distributed(config, dist_obj, batch, offsets,
                                   train_idx, train_weights, W0, l2s, l1v):
    """Mesh variant of the swept solve: lanes lax.map-loop around the
    shard_mapped objective (no batching rule through shard_map); the
    sharded batch stays resident across every lane."""
    from photon_ml_tpu.optim.lbfgs import lbfgs_solve_swept

    view = _apply_training_view(batch, offsets, train_idx, train_weights)

    def vg(w, l2):
        obj = dist_obj.objective
        o = dist_obj.replace(objective=obj.replace(
            reg=obj.reg.replace(l2_weight=l2)))
        return o.value_and_gradient(w, view)

    return lbfgs_solve_swept(vg, W0, l2s, config, l1_weights=l1v,
                             use_map=True)


@jax.jit
def _score_batch(batch, w: Array) -> Array:
    return batch.x_dot(w)


@jax.jit
def _score_batch_distributed(dist_obj, batch, w: Array) -> Array:
    """Sharded scoring: per-shard layouts (GRR plan / colmajor) index
    only their device's rows, so X·w must run under shard_map.  Module
    -level jit so per-CD-iteration scoring hits the compile cache."""
    return dist_obj.x_dot(w, batch)


def _re_block_batch(blocks, b: int, offsets: Array) -> DenseBatch:
    """Bucket b's entity blocks as one vmappable DenseBatch, with
    per-example offsets scattered into block space."""
    (x_blocks, label_blocks, weight_blocks, mask_blocks,
     ex_idx, row_idx, col_idx) = blocks
    off_blk = jnp.zeros_like(label_blocks[b]).at[
        row_idx[b], col_idx[b]
    ].set(offsets[ex_idx[b]])
    return DenseBatch(
        x=x_blocks[b], labels=label_blocks[b], weights=weight_blocks[b],
        offsets=off_blk, mask=mask_blocks[b],
    )


def _block_scores(x: Array, w: Array) -> Array:
    """[E, cap] scores of a bucket's blocks [E, cap, p] under its
    coefficients [E, p], in float32 whatever the width
    (``data.batch.F32``)."""
    return jnp.einsum("ecp,ep->ec", x, w, precision=F32)


# The vmapped solve of a bucket compiles in time that grows faster than
# its entity count (TPU compiler, described v5e: 9 s at 16,384 entities,
# 52 s at 65,536, 148 s at 182,062, its temporaries padded to whole
# tiles per entity).  A bucket over _ONE_SHOT_ENTITIES is therefore
# solved _CHUNK_ENTITIES at a time by one loop body.  Buckets up to
# _ONE_SHOT_ENTITIES keep the one-shot program as it has always been
# compiled and measured; lowering that bound is a change to every such
# program, to be measured as one.
_ONE_SHOT_ENTITIES = 131_072
_CHUNK_ENTITIES = 16_384


def _bucket_chunks(n: int) -> tuple[int, int]:
    """(chunks, lanes a chunk) in which ``_solve_bucket`` solves a
    bucket of ``n`` entities."""
    if n <= _ONE_SHOT_ENTITIES:
        return 1, n
    n_chunks = -(-n // _CHUNK_ENTITIES)
    return n_chunks, -(-n // n_chunks)


def _solve_bucket(run, batch: DenseBatch, w0: Array, prior=None):
    """``vmap(run)`` over a bucket's entities; a bucket over
    ``_ONE_SHOT_ENTITIES`` in equal chunks of at most
    ``_CHUNK_ENTITIES``, one after the other (``lax.map``).  Entities
    are independent and every update of the solver is guarded per lane,
    so an entity's result does not depend on its neighbours; the lanes
    that pad the last chunk hold no example (mask 0) and are cut off.

    ``prior``, where given, is the bucket's (means, precisions) blocks,
    [E_b, p_b] each, handed to ``run`` as its third and fourth
    arguments, row for row with the entities.  A chunk gathers its rows
    of them out of the resident blocks (no padded copy of either); a
    lane that pads the last chunk gets precision 0."""
    n = w0.shape[0]
    n_chunks, lanes = _bucket_chunks(n)
    if n_chunks == 1:
        return jax.vmap(run)(batch, w0, *(prior or ()))

    def chunked(a):
        pad = [(0, n_chunks * lanes - n)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad).reshape(n_chunks, lanes, *a.shape[1:])

    if prior is None:
        out = jax.lax.map(lambda args: jax.vmap(run)(*args),
                          jax.tree.map(chunked, (batch, w0)))
    else:
        def chunk(args):
            chunk_batch, chunk_w0, c = args
            rows = c * lanes + jnp.arange(lanes)
            at = jnp.minimum(rows, n - 1)
            means, precisions = (jnp.take(a, at, axis=0) for a in prior)
            precisions = jnp.where((rows < n)[:, None], precisions, 0.0)
            return jax.vmap(run)(chunk_batch, chunk_w0, means, precisions)

        out = jax.lax.map(chunk, (*jax.tree.map(chunked, (batch, w0)),
                                  jnp.arange(n_chunks)))
    return jax.tree.map(
        lambda a: a.reshape(n_chunks * lanes, *a.shape[2:])[:n], out)


def _toward(objective, prior, fn):
    """``fn(objective, ...)`` as a per-entity function of
    ``(..., means, precisions)``: the objective with that entity's
    prior installed (``EntityPriors.entity``)."""
    def entity(*args):
        *rest, means, precisions = args
        return fn(objective.replace(prior=prior.entity(means, precisions)),
                  *rest)
    return entity


def _re_train_impl(optimizer, config, has_l1, objective, blocks,
                   offsets: Array, w0s: list[Array], prior=None):
    """Every bucket's vmapped solve.  ``prior`` (``EntityPriors``, or
    None for a random effect without one, whose program is then the one
    it has always been) adds each entity's Gaussian prior to its
    objective."""
    problem = OptimizationProblem(
        objective=objective, optimizer=optimizer, config=config
    )
    if prior is None:
        run = partial(problem.run, has_l1=has_l1)
        return [
            _solve_bucket(run, _re_block_batch(blocks, b, offsets), w0s[b])
            for b in range(len(blocks[0]))
        ]
    run = _toward(objective, prior, lambda obj, batch, w0: problem.replace(
        objective=obj).run(batch, w0, has_l1=has_l1))
    return [
        _solve_bucket(run, _re_block_batch(blocks, b, offsets), w0s[b],
                      (prior.means[b], prior.precisions[b]))
        for b in range(len(blocks[0]))
    ]


_re_train, _re_train_donating = _jit_solve(
    _re_train_impl, donate_argnums=(6,))  # w0s blocks


# -- streamed-RE per-chunk programs (ISSUE 5) -------------------------------
# One compiled program per (bucket shape, optimizer config): every entity
# chunk of a bucket is congruent [C, cap_b, p_b], so the vmapped masked
# while_loop solve replays one executable chunk after chunk, exactly as
# the fixed-effect streaming tier replays its per-chunk objective.


def _re_chunk_train_impl(optimizer, config, has_l1, objective, x, labels,
                         weights, mask, offsets, w0):
    problem = OptimizationProblem(
        objective=objective, optimizer=optimizer, config=config
    )
    batch = DenseBatch(x=x, labels=labels, weights=weights,
                       offsets=offsets, mask=mask)
    res = jax.vmap(partial(problem.run, has_l1=has_l1))(batch, w0)
    # Scores and per-entity movement come out of the SAME dispatch: the
    # chunk is already in device memory, so the CD sweep never pays a
    # second scoring pass over the store.
    scores = _block_scores(x, res.w)
    dw = jnp.max(jnp.abs(res.w - w0), axis=-1)
    return res.w, scores, dw, res.converged, res.iterations


_re_chunk_train = jax.jit(_re_chunk_train_impl, static_argnums=(0, 1, 2))


@jax.jit
def _re_chunk_score(x, w):
    return _block_scores(x, w)


@jax.jit
def _re_chunk_vars(objective, x, labels, weights, mask, offsets, w):
    from photon_ml_tpu.optim.variance import simple_variances

    batch = DenseBatch(x=x, labels=labels, weights=weights,
                       offsets=offsets, mask=mask)
    return jax.vmap(
        lambda w_, b_: simple_variances(objective, w_, b_)
    )(w, batch)


def _entity_example_runs(ex_sorted_b: np.ndarray, starts_b: np.ndarray,
                         ents: np.ndarray):
    """Vectorized (example ids, chunk rows, within-entity cols) for the
    entities ``ents`` (global bucket slots) — the index maps that move
    per-example offsets into block space and block scores back out.
    ``ex_sorted_b`` orders the bucket's examples by (entity slot,
    within-entity position), so each entity is one contiguous run and
    any packed chunk's map is O(examples) numpy arithmetic."""
    counts = (starts_b[ents + 1] - starts_b[ents]).astype(np.int64)
    total = int(counts.sum())
    rows = np.repeat(np.arange(len(ents), dtype=np.int64), counts)
    cum = np.cumsum(counts) - counts
    cols = np.arange(total, dtype=np.int64) - np.repeat(cum, counts)
    idx = np.repeat(starts_b[ents], counts) + cols
    return ex_sorted_b[idx], rows, cols


def _example_runs(grouping: EntityGrouping):
    """Per-bucket (ex_sorted, ent_starts) run maps (see
    ``_entity_example_runs``)."""
    ex_sorted, ent_starts = [], []
    for b, ne in enumerate(grouping.n_entities):
        sel = np.flatnonzero(grouping.example_bucket == b)
        order = np.lexsort((grouping.example_col[sel],
                            grouping.example_row[sel]))
        sel = sel[order].astype(np.int64)
        ex_sorted.append(sel)
        counts = np.bincount(grouping.example_row[sel], minlength=ne)
        starts = np.zeros(ne + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        ent_starts.append(starts)
    return ex_sorted, ent_starts


@partial(jax.jit, static_argnums=0)
def _re_score(n_examples: int, x_blocks, ex_idx, row_idx, col_idx,
              coefficient_blocks) -> Array:
    scores = jnp.zeros((n_examples,), jnp.float32)
    for b, w_b in enumerate(coefficient_blocks):
        blk_scores = _block_scores(x_blocks[b], w_b)
        scores = scores.at[ex_idx[b]].set(
            blk_scores[row_idx[b], col_idx[b]]
        )
    return scores


@jax.jit
def _re_variances(objective, blocks, coefficient_blocks, offsets: Array,
                  prior=None):
    """SIMPLE variances of every bucket's entities, 1 / diag H at their
    coefficients; with ``prior`` (``EntityPriors``) its precisions are
    part of that diagonal."""
    from photon_ml_tpu.optim.variance import simple_variances

    if prior is None:
        return [
            jax.vmap(
                lambda w, bb: simple_variances(objective, w, bb)
            )(w_b, _re_block_batch(blocks, b, offsets))
            for b, w_b in enumerate(coefficient_blocks)
        ]
    entity = _toward(objective, prior, simple_variances)
    return [
        jax.vmap(entity)(w_b, _re_block_batch(blocks, b, offsets),
                         prior.means[b], prior.precisions[b])
        for b, w_b in enumerate(coefficient_blocks)
    ]


class Coordinate:
    """train/score contract (reference ``Coordinate`` abstraction)."""

    name: str

    # True when this coordinate's solver emits its own convergence
    # telemetry (the host-driven streaming solvers / streamed REs);
    # the CD loop then skips its resident-result trace so one solve
    # never lands twice in the log (ISSUE 8).
    traces_convergence = False

    def initial_coefficients(self):
        raise NotImplementedError

    def train(self, offsets: Array, warm_start):
        """offsets [n] (residual scores from other coordinates) → (model
        coefficients, optimizer diagnostics)."""
        raise NotImplementedError

    def score(self, coefficients) -> Array:
        """coefficients → per-example scores [n]."""
        raise NotImplementedError

    def train_counts(self) -> dict:
        """What the ``coord_train`` stage says of the shape of this
        coordinate's solves, beside what the solver reports; nothing
        by default."""
        return {}

    def retire_converged(self) -> int | None:
        """Commit this sweep's converged-entity retirement candidates
        (the coordinate-descent between-sweeps hook).  Base contract:
        no retirement protocol — returns None; the streamed
        random-effect coordinate overrides with the number of newly
        frozen entities."""
        return None


@dataclasses.dataclass(eq=False)
class FixedEffectCoordinate(Coordinate):
    """Global solve over the full batch (reference
    ``FixedEffectCoordinate`` + ``DistributedOptimizationProblem``)."""

    name: str
    batch: Batch                      # full batch (scoring); local or sharded
    problem: OptimizationProblem
    distributed: DistributedGLMObjective | None = None  # set if sharded
    # Down-sampled training view (reference DownSampler, SURVEY §2.4):
    # train on batch rows ``train_idx`` with ``train_weights``; score all.
    train_idx: Array | None = None
    train_weights: Array | None = None
    # Real example count when the batch rows were padded (mesh sharding
    # pads n to a multiple of the device count); scores are sliced back
    # to example space so they stay summable with other coordinates'.
    n_examples: int | None = None

    def initial_coefficients(self) -> Array:
        return jnp.zeros((self.batch.dim,), jnp.float32)

    def _training_batch(self, offsets: Array) -> Batch:
        return _apply_training_view(self.batch, offsets, self.train_idx,
                                    self.train_weights)

    def train(self, offsets: Array, warm_start: Array | None = None,
              donate_warm_start: bool = False):
        w0 = self.initial_coefficients() if warm_start is None else warm_start
        has_l1 = self.problem.has_l1()
        if self.distributed is None:
            fn = (_fixed_train_local_donating if donate_warm_start
                  else _fixed_train_local)
            res = fn(
                self.problem.optimizer, self.problem.config, has_l1,
                self.problem.objective, self.batch, offsets,
                self.train_idx, self.train_weights, w0,
            )
        else:
            fn = (_fixed_train_distributed_donating if donate_warm_start
                  else _fixed_train_distributed)
            res = fn(
                self.problem.optimizer, self.problem.config, has_l1,
                self.distributed, self.batch, offsets,
                self.train_idx, self.train_weights, w0,
            )
        return res.w, res

    def train_swept(self, offsets: Array, reg, warm_start=None):
        """Train the whole λ grid as ONE batched solve: L stacked
        coefficient lanes share every objective evaluation against the
        same training view (one data stream amortized across the grid).

        Args:
          offsets: [n] shared residual scores (the λ sweep varies only
            regularization, so all lanes see the same offsets).
          reg: ``ops.regularization.SweptRegularization`` — per-lane
            (l1, l2) weight splits, one lane per grid point.
          warm_start: optional [L, dim] stacked starting points
            (continuation across tuning rounds).

        Returns (W [L, dim], batched OptimizationResult).
        """
        from photon_ml_tpu.data.batch import SparseBatch
        from photon_ml_tpu.optim.base import OptimizerType

        if self.problem.optimizer == OptimizerType.TRON:
            raise ValueError(
                "train_swept supports LBFGS/OWL-QN lanes only (the λ "
                "sweep is the L-BFGS grid workload; fit TRON "
                "coordinates per grid point)")
        L = reg.n_lanes
        dim = self.batch.dim
        W0 = (jnp.zeros((L, dim), jnp.float32) if warm_start is None
              else jnp.asarray(warm_start, jnp.float32))
        l1v = (reg.l1_vectors(dim, self.problem.objective.reg.reg_mask)
               if reg.has_l1() else None)
        if self.distributed is not None:
            res = _fixed_train_swept_distributed(
                self.problem.config, self.distributed, self.batch,
                offsets, self.train_idx, self.train_weights, W0,
                reg.l2_weights, l1v,
            )
        else:
            # GRR-plan batches lane-loop (lax.map): the Mosaic kernel
            # has no batching rule; the plan stays HBM-resident across
            # lanes either way.
            use_map = (isinstance(self.batch, SparseBatch)
                       and self.batch.grr is not None)
            res = _fixed_train_swept(
                self.problem.config, use_map, self.problem.objective,
                self.batch, offsets, self.train_idx, self.train_weights,
                W0, reg.l2_weights, l1v,
            )
        return res.w, res

    def score(self, coefficients: Array) -> Array:
        if self.distributed is not None:
            scores = _score_batch_distributed(
                self.distributed, self.batch, coefficients)
        else:
            scores = _score_batch(self.batch, coefficients)
        if (self.n_examples is not None
                and self.n_examples != self.batch.n_padded):
            scores = scores[: self.n_examples]
        return scores

    def as_model(self, coefficients: Array) -> FixedEffectModel:
        return FixedEffectModel(
            coefficients=Coefficients(means=coefficients),
            feature_shard=self.name,
        )

    def compute_variances(self, coefficients: Array, offsets: Array,
                          variance_type) -> Array | None:
        """Coefficient variances at the optimum over the training view
        (reference VarianceComputationType pipeline, SURVEY §2.1).

        Under mesh sharding the distributed objective must aggregate
        the Hessian quantities (its colmajor row indices are
        shard-local, and the diagonal is a cross-shard sum)."""
        from photon_ml_tpu.optim.variance import compute_variances

        obj = self.distributed or self.problem.objective
        return compute_variances(
            obj, coefficients, self._training_batch(offsets), variance_type,
        )


@dataclasses.dataclass(eq=False)
class ChunkedFixedEffectCoordinate(Coordinate):
    """Fixed effect trained by chunk-accumulated streaming — the
    beyond-HBM-residency class (reference: Spark streams splits through
    executors, SURVEY §1 L1/§5.8; see ``data.chunked_batch``).

    Same ``train``/``score`` contract as ``FixedEffectCoordinate``; the
    solve is the host-driven ``optim.streaming.streaming_lbfgs_solve``
    over a ``ChunkedGLMObjective`` (per-chunk device programs, exact
    chunk-accumulated objective), or ``streaming_tron_solve`` when the
    optimizer is TRON (ISSUE 17: chunk-accumulated Hessian-vector
    passes feed the Steihaug-CG inner loop, Jacobi-preconditioned from
    the Hessian-diagonal pass).  When the chunked batch is disk-spilled
    (``spill_dir`` — the out-of-core tier), every training AND
    ``_per_example`` scoring sweep runs the async disk→host→device
    prefetch pipeline, ``prefetch_depth`` chunks ahead.  Down-sampling
    views are not supported on this path (documented config error);
    TRON λ-sweeps stay per-grid-point (``train_swept`` is the L-BFGS
    lane workload, as on the resident path)."""

    name: str
    chunked: "object"                 # data.chunked_batch.ChunkedBatch
    objective: GLMObjective           # reg/prior included (added once)
    optimizer: "object"               # OptimizerType
    config: OptimizerConfig
    max_resident: int = 1
    prefetch_depth: int = 2

    traces_convergence = True         # the streaming solvers emit live

    def __post_init__(self):
        from photon_ml_tpu.optim.streaming import ChunkedGLMObjective

        self._obj = ChunkedGLMObjective(
            self.objective, self.chunked, max_resident=self.max_resident,
            prefetch_depth=self.prefetch_depth)

    @property
    def problem(self) -> OptimizationProblem:
        """Estimator-facing surface parity with FixedEffectCoordinate
        (model export reads ``coord.problem.objective.norm``)."""
        return OptimizationProblem(
            objective=self.objective, optimizer=self.optimizer,
            config=self.config)

    def initial_coefficients(self) -> Array:
        return jnp.zeros((self.chunked.dim,), jnp.float32)

    def _coerce_offsets(self, offsets) -> np.ndarray:
        """Offsets → exactly ``chunked.n`` entries.  Over-long arrays
        are accepted ONLY when the length matches the known padding
        grid (the chunk grid, which already folds in the mesh's device
        rounding) — anything else is a caller bug that silent
        truncation would turn into wrong training data (advisor
        finding); under-long arrays fail in ``set_offsets``."""
        off = np.asarray(offsets, np.float32)
        n = self.chunked.n
        if off.shape[0] == n:
            return off
        grid = self.chunked.n_chunks * self.chunked.chunk_rows
        if off.shape[0] == grid:
            return off[:n]
        if off.shape[0] > n:
            raise ValueError(
                f"offsets length {off.shape[0]} exceeds n {n} and does "
                f"not match the chunk padding grid {grid}")
        return off

    def train(self, offsets: Array, warm_start: Array | None = None,
              donate_warm_start: bool = False):
        from photon_ml_tpu.optim.base import OptimizerType
        from photon_ml_tpu.optim.streaming import (
            streaming_lbfgs_solve,
            streaming_tron_solve,
        )

        self.chunked.set_offsets(self._coerce_offsets(offsets))
        self._obj.invalidate()
        w0 = (self.initial_coefficients() if warm_start is None
              else warm_start)
        problem = self.problem
        l1 = (problem._l1_vector(self.chunked.dim) if problem.has_l1()
              else None)
        if self.optimizer == OptimizerType.TRON:
            if l1 is not None:
                raise ValueError(
                    "TRON supports smooth objectives only (no L1) — "
                    "as on the resident path")
            res = streaming_tron_solve(
                self._obj.value_and_gradient, self._obj.hvp_pass, w0,
                self.config, hessian_diag=self._obj.hessian_diagonal,
                label=self.name)
        else:
            res = streaming_lbfgs_solve(
                self._obj.value_and_gradient, w0, self.config,
                l1_weight=l1, value_fn=self._obj.value, label=self.name)
        return res.w, res

    def train_swept(self, offsets: Array, reg, warm_start=None):
        """Batched λ-sweep on the chunked path: ONE double-buffered
        chunk sweep per objective evaluation feeds all L lanes
        (``ChunkedGLMObjective.value_and_gradient_swept``) — the grid's
        data passes per solver iteration drop from L to ~1.

        Same contract as ``FixedEffectCoordinate.train_swept``.
        """
        from photon_ml_tpu.optim.base import OptimizerType
        from photon_ml_tpu.optim.streaming import (
            streaming_lbfgs_solve_swept,
        )

        if self.optimizer == OptimizerType.TRON:
            raise ValueError(
                "train_swept supports LBFGS/OWL-QN lanes only (the λ "
                "sweep is the L-BFGS grid workload; fit TRON "
                "coordinates per grid point)")
        self.chunked.set_offsets(self._coerce_offsets(offsets))
        self._obj.invalidate()
        L = reg.n_lanes
        W0 = (jnp.zeros((L, self.chunked.dim), jnp.float32)
              if warm_start is None
              else jnp.asarray(warm_start, jnp.float32))
        l1v = (reg.l1_vectors(self.chunked.dim,
                              self.objective.reg.reg_mask)
               if reg.has_l1() else None)
        res = streaming_lbfgs_solve_swept(
            lambda W: self._obj.value_and_gradient_swept(W, reg),
            lambda W: self._obj.value_swept(W, reg),
            W0, self.config, l1_weights=l1v, label=self.name,
        )
        return res.w, res

    def score(self, coefficients: Array) -> Array:
        """Raw X·w per example — offset-free, the same
        ``CoordinateDataScores`` convention as the resident path."""
        return jnp.asarray(self._obj.x_dot(coefficients))

    def as_model(self, coefficients: Array) -> FixedEffectModel:
        return FixedEffectModel(
            coefficients=Coefficients(means=coefficients),
            feature_shard=self.name,
        )

    def compute_variances(self, coefficients: Array, offsets: Array,
                          variance_type) -> Array | None:
        from photon_ml_tpu.optim.variance import VarianceComputationType

        if variance_type == VarianceComputationType.NONE:
            return None
        if variance_type == VarianceComputationType.FULL:
            raise ValueError(
                "FULL variances materialize a [d, d] Hessian — not "
                "supported on the chunked path; use SIMPLE")
        self.chunked.set_offsets(self._coerce_offsets(offsets))
        self._obj.invalidate()
        diag = self._obj.hessian_diagonal(coefficients)
        return 1.0 / jnp.maximum(diag, 1e-12)


@dataclasses.dataclass(eq=False)
class RandomEffectCoordinate(Coordinate):
    """Entity-sharded solves, one vmapped batch per size bucket
    (reference ``RandomEffectCoordinate``)."""

    name: str
    grouping: EntityGrouping
    # Per-bucket device arrays (built by ``build_random_effect_coordinate``):
    # widths may differ per bucket when a subspace projection is applied.
    x_blocks: list[Array]        # [E_b, cap_b, p_b]
    label_blocks: list[Array]    # [E_b, cap_b]
    weight_blocks: list[Array]   # [E_b, cap_b]
    mask_blocks: list[Array]     # [E_b, cap_b]
    # Static per-bucket example-index maps (example space ↔ block space):
    ex_idx: list[Array]          # [n_b] example positions in this bucket
    row_idx: list[Array]         # [n_b] entity slot
    col_idx: list[Array]         # [n_b] within-entity position
    n_examples: int
    problem: OptimizationProblem
    # Set when features were subspace-projected (sparse global shard):
    projection: "SubspaceProjection | None" = None
    # Each entity's Gaussian prior toward a previous model (incremental
    # training, ops/prior.py), blocks shaped as the coefficient blocks;
    # ``prior_entities`` of the entities have one.
    prior: "EntityPriors | None" = None
    prior_entities: int = 0

    def initial_coefficients(self) -> list[Array]:
        return [
            jnp.zeros((blk.shape[0], blk.shape[-1]), jnp.float32)
            for blk in self.x_blocks
        ]

    def _blocks(self):
        return (self.x_blocks, self.label_blocks, self.weight_blocks,
                self.mask_blocks, self.ex_idx, self.row_idx, self.col_idx)

    def train(self, offsets: Array, warm_start=None,
              donate_warm_start: bool = False):
        w0s = self.initial_coefficients() if warm_start is None else warm_start
        fn = _re_train_donating if donate_warm_start else _re_train
        results = fn(
            self.problem.optimizer, self.problem.config,
            self.problem.has_l1(), self.problem.objective,
            self._blocks(), offsets, w0s,
            *(() if self.prior is None else (self.prior,)),
        )
        return [r.w for r in results], results

    def train_counts(self) -> dict:
        """``buckets``: the bucket programs a ``train`` runs;
        ``chunks``: the ``_solve_bucket`` chunks they are solved in;
        with a prior, ``prior_entities``: the entities that have one."""
        counts = {"buckets": len(self.x_blocks),
                  "chunks": sum(_bucket_chunks(blk.shape[0])[0]
                                for blk in self.x_blocks)}
        if self.prior is not None:
            counts["prior_entities"] = self.prior_entities
        return counts

    def score(self, coefficient_blocks: list[Array]) -> Array:
        """Block-space scoring: x·w per entity block, gathered back to
        example order (works for projected and unprojected widths)."""
        return _re_score(self.n_examples, self.x_blocks, self.ex_idx,
                         self.row_idx, self.col_idx, coefficient_blocks)

    def as_model(self, coefficient_blocks: list[Array]) -> RandomEffectModel:
        return RandomEffectModel(
            coefficient_blocks=coefficient_blocks,
            grouping=self.grouping,
            feature_shard=self.name,
            projection=self.projection,
        )

    def compute_variance_blocks(
        self, coefficient_blocks: list[Array], offsets: Array
    ) -> list[Array]:
        """SIMPLE per-entity variances (1/diag H, the prior's precision
        included), vmapped per bucket — the per-entity arm of the
        reference's variance pipeline."""
        return _re_variances(self.problem.objective, self._blocks(),
                             coefficient_blocks, offsets,
                             *(() if self.prior is None else (self.prior,)))

    @property
    def coefficient_shapes(self) -> list[tuple[int, int]]:
        """(entities, width) per bucket — the shape contract shared
        with the streamed coordinate (warm-start import sizes its
        zero blocks from this, not from resident x_blocks)."""
        return [(blk.shape[0], blk.shape[-1]) for blk in self.x_blocks]


@dataclasses.dataclass(eq=False)
class StreamedRandomEffectCoordinate(Coordinate):
    """Out-of-core random-effect training: streamed entity-bucket
    solves with converged-entity retirement (ISSUE 5 tentpole).

    The resident ``RandomEffectCoordinate`` holds every bucket's
    ``[E_b, cap_b, p_b]`` entity blocks in device/host memory for the
    whole descent — the last subsystem still capped at the resident
    class.  Here each bucket's entities are split into fixed-shape
    *entity chunks* (``chunk_entities`` per chunk, last chunk padded
    with zero-mask entities), spilled through ``data.chunk_store``
    (entity-block codec; content-keyed, mmap-loaded, LRU
    ``host_max_resident`` window, lineage rebuild, warm across runs)
    and driven chunk-by-chunk through the round-8 prefetch pipeline
    (``optim.streaming.prefetch_stream``: disk read → host staging →
    async device_put under the previous chunk's solve).  Only the
    coefficient blocks ``[E_b, p_b]``, the per-example run maps, and
    the score plane stay resident, so host/HBM footprint is bounded by
    the window instead of E.

    **Converged-entity retirement**: between CD sweeps
    (``retire_converged``, called by the coordinate-descent loop),
    entities whose coefficients AND offsets moved less than the solver
    tolerance are retired into a frozen set — their scores stay folded
    into the totals (x and w are unchanged, so the cached scores are
    exact) while subsequent sweeps re-pack only the ACTIVE entities
    into chunks.  Per-sweep solve work shrinks as the descent
    converges, and one hard entity no longer keeps thousands of
    converged lanes spinning through the masked while_loop.  Retired
    entities wake up if their offsets later drift by more than the
    tolerance, so retirement can never move the final model beyond
    solver tolerance.
    """

    traces_convergence = True        # re_convergence events per sweep

    name: str
    grouping: EntityGrouping
    problem: OptimizationProblem
    store: "object"                  # data.chunk_store.ChunkStore
    # Entities per chunk, PER BUCKET: the requested ``re_chunk_entities``
    # balanced across each bucket's chunk count and capped by the
    # bucket's entity count (a global chunk size would pad a small
    # bucket's chunks with dead solve lanes — at cap 1024 that is real
    # FLOPs and real bytes), then rounded up to the mesh grid.
    chunk_ents: list[int]
    widths: list[int]                # p_b per bucket
    ex_sorted: list[np.ndarray]      # per bucket [n_b] example ids
    ent_starts: list[np.ndarray]     # per bucket [E_b + 1] run starts
    chunk_base: list[int]            # global chunk-id base per bucket
    n_source_chunks: list[int]       # chunks per bucket
    n_examples: int
    mesh: "object | None" = None
    prefetch_depth: int = 2
    retirement: bool = True
    # Coefficient/offset movement threshold for retirement; None =
    # the solver tolerance (the ISSUE contract).
    retire_tolerance: float | None = None
    projection: "SubspaceProjection | None" = None

    def __post_init__(self):
        if self.retire_tolerance is None:
            self.retire_tolerance = float(self.problem.config.tolerance)
        ne = self.grouping.n_entities
        self._w_host = [np.zeros((e, p), np.float32)
                        for e, p in zip(ne, self.widths)]
        self._active = [np.ones(e, bool) for e in ne]
        self._pending = [np.zeros(e, bool) for e in ne]
        self._scores_host = np.zeros(self.n_examples, np.float32)
        self._solved_offsets: np.ndarray | None = None
        self._prev_offsets: np.ndarray | None = None
        # The blocks the last train() returned, held BY REFERENCE (an
        # id()-only key could match a recycled address after GC and
        # serve stale cached scores / skip warm-start adoption).
        self._last_w_blocks: list | None = None
        self._cached_scores: Array | None = None
        self.last_diag: dict = {}

    def _is_last_train_output(self, blocks) -> bool:
        return (self._last_w_blocks is not None
                and len(blocks) == len(self._last_w_blocks)
                and all(a is b for a, b in zip(blocks,
                                               self._last_w_blocks)))

    # -- shape/contract surface -------------------------------------------

    @property
    def coefficient_shapes(self) -> list[tuple[int, int]]:
        return [(w.shape[0], w.shape[1]) for w in self._w_host]

    def initial_coefficients(self) -> list[Array]:
        return [jnp.zeros((e, p), jnp.float32)
                for e, p in zip(self.grouping.n_entities, self.widths)]

    @property
    def entities_retired(self) -> int:
        return int(sum((~a).sum() for a in self._active))

    # -- index/run helpers --------------------------------------------------

    def _entity_max(self, b: int, per_example: np.ndarray) -> np.ndarray:
        """Per-entity max of a per-example quantity over bucket b's
        runs ([E_b]; one vectorized reduceat, no Python per entity)."""
        v = per_example[self.ex_sorted[b]]
        return np.maximum.reduceat(v, self.ent_starts[b][:-1])

    @property
    def chunk_entities(self) -> int:
        """Largest per-bucket chunk size (display/diagnostics)."""
        return max(self.chunk_ents) if self.chunk_ents else 0

    def _specs(self) -> list[tuple[int, np.ndarray]]:
        """Packed chunk plan for this sweep: active entities of each
        bucket, ascending slot order, ``chunk_ents[b]`` per chunk —
        ascending slots keep source-chunk access sequential, so the
        LRU window streams forward exactly like a fixed-effect sweep."""
        specs = []
        for b, act in enumerate(self._active):
            C = self.chunk_ents[b]
            sel = np.flatnonzero(act)
            for lo in range(0, len(sel), C):
                specs.append((b, sel[lo:lo + C]))
        return specs

    def _assemble(self, spec, offsets: np.ndarray, with_w0: bool = True,
                  x_only: bool = False):
        """Load stage (runs on the prefetch thread): pull the source
        chunk(s) from the store window, gather the active entities'
        rows into one fixed-shape packed chunk, scatter the CURRENT
        offsets into block space, and gather the warm-start lanes from
        the resident coefficients.  A full, untouched source chunk
        passes its (possibly memmap) arrays straight through — the
        all-active steady state costs no host copy.  ``x_only`` skips
        the scalar planes and the offsets scatter for consumers that
        read nothing but ``x`` (the foreign-blocks scoring pass)."""
        b, ents = spec
        C = self.chunk_ents[b]
        cap = self.grouping.capacities[b]
        p = self.widths[b]
        base = self.chunk_base[b]
        src = ents // C
        full = (len(ents) == C and src[0] == src[-1]
                and int(ents[0]) == int(src[0]) * C
                and int(ents[-1]) == int(src[0]) * C + C - 1)
        if full:
            ch = self.store.get(base + int(src[0]))
            x = ch["x"]
            if not x_only:
                lab, wt, mk = ch["labels"], ch["weights"], ch["mask"]
        else:
            x = np.zeros((C, cap, p), np.float32)
            if not x_only:
                lab = np.zeros((C, cap), np.float32)
                wt = np.zeros((C, cap), np.float32)
                mk = np.zeros((C, cap), np.float32)
            for s in np.unique(src):          # ascending: LRU-friendly
                m = src == s
                ch = self.store.get(base + int(s))
                rows_local = (ents[m] - int(s) * C).astype(np.int64)
                dst = np.flatnonzero(m)
                x[dst] = ch["x"][rows_local]
                if not x_only:
                    lab[dst] = ch["labels"][rows_local]
                    wt[dst] = ch["weights"][rows_local]
                    mk[dst] = ch["mask"][rows_local]
        ex, rows, cols = _entity_example_runs(
            self.ex_sorted[b], self.ent_starts[b], ents)
        if x_only:
            arrays = {"x": x}
        else:
            off = np.zeros((C, cap), np.float32)
            off[rows, cols] = offsets[ex]
            arrays = {"x": x, "labels": lab, "weights": wt, "mask": mk,
                      "offsets": off}
        if with_w0:
            w0 = np.zeros((C, p), np.float32)
            w0[: len(ents)] = self._w_host[b][ents]
            arrays["w0"] = w0
        return {"arrays": arrays, "b": b, "ents": ents, "ex": ex,
                "rows": rows, "cols": cols}

    def _place(self, item):
        """Device placement stage: async device_put (entity-sharded on
        the mesh); the host index maps ride alongside for the
        consumer's score scatter."""
        from photon_ml_tpu.parallel.mesh import place_entity_chunk

        dev = place_entity_chunk(item["arrays"], self.mesh)
        return (dev, item["b"], item["ents"], item["ex"], item["rows"],
                item["cols"])

    def _stream(self, specs, offsets: np.ndarray, with_w0: bool = True,
                x_only: bool = False):
        from photon_ml_tpu.optim.streaming import prefetch_stream

        load = lambda j: self._assemble(specs[j], offsets, with_w0,
                                        x_only)
        return prefetch_stream(load, self._place, range(len(specs)),
                               self.prefetch_depth, store=self.store)

    # -- train ---------------------------------------------------------------

    def _adopt_warm_start(self, warm_start) -> None:
        """External warm-start coefficients (saved model import,
        checkpoint resume): overwrite the resident blocks and reset the
        retirement state — the movement bookkeeping the retirement
        decision rests on is no longer about these coefficients."""
        for b, w in enumerate(warm_start):
            wb = np.asarray(w, np.float32)
            if wb.shape != self._w_host[b].shape:
                raise ValueError(
                    f"warm-start bucket {b} shape {wb.shape} != "
                    f"{self._w_host[b].shape}")
            self._w_host[b] = wb.copy()
        for b in range(len(self._active)):
            self._active[b][:] = True
            self._pending[b][:] = False
        self._solved_offsets = None
        self._prev_offsets = None

    def train(self, offsets: Array, warm_start=None,
              donate_warm_start: bool = False):
        """One streamed sweep over the ACTIVE entities.  Scores come
        out of the same per-chunk dispatch as the solve (no second
        store pass); ``donate_warm_start`` is accepted for contract
        parity and ignored (training state is host-resident)."""
        del donate_warm_start
        off = np.asarray(offsets, np.float32)
        if off.shape[0] != self.n_examples:
            raise ValueError(f"offsets length {off.shape[0]} != "
                             f"n {self.n_examples}")
        if warm_start is not None and not self._is_last_train_output(
                list(warm_start)):
            self._adopt_warm_start(warm_start)
        rtol = self.retire_tolerance
        woken = 0
        if self._solved_offsets is None:
            self._solved_offsets = off.copy()
        elif self.retirement and self.entities_retired:
            # Wake retired entities whose offsets drifted past the
            # tolerance since their last solve — retirement must never
            # move the final model beyond solver tolerance.  (Skipped
            # while nothing is retired: the drift scan is O(n) per
            # bucket.)
            drift = np.abs(off - self._solved_offsets)
            for b in range(len(self._active)):
                woke = ((~self._active[b])
                        & (self._entity_max(b, drift) >= rtol))
                woken += int(woke.sum())
                self._active[b] |= woke

        specs = self._specs()
        retired_now = self.entities_retired
        ne = self.grouping.n_entities
        solved = [np.zeros(e, bool) for e in ne]
        conv = [np.zeros(e, bool) for e in ne]
        dw = [np.zeros(e, np.float32) for e in ne]
        max_iters = 0

        def harvest(out, b, ents, ex, rows, cols):
            nonlocal max_iters
            k = len(ents)
            w_np = np.asarray(out[0])[:k]
            scores_np = np.asarray(out[1])
            self._w_host[b][ents] = w_np
            self._scores_host[ex] = scores_np[rows, cols]
            dw[b][ents] = np.asarray(out[2])[:k]
            solved[b][ents] = True
            conv[b][ents] = np.asarray(out[3])[:k]
            if k:
                max_iters = max(max_iters,
                                int(np.asarray(out[4])[:k].max()))
            self._solved_offsets[ex] = off[ex]

        opt = self.problem
        has_l1 = opt.has_l1()
        pending = None
        # Stage span (ISSUE 7): one streamed RE sweep — the unit the
        # overlap-efficiency derivation divides consumer wait by.
        with telemetry.span("re_sweep", cat="solver",
                            coordinate=self.name, chunks=len(specs)):
            for ci, (_, item) in enumerate(self._stream(specs, off)):
                dev, b, ents, ex, rows, cols = item
                with telemetry.span("chunk_compute", cat="device",
                                    bucket=b):
                    out = _re_chunk_train(
                        opt.optimizer, opt.config, has_l1, opt.objective,
                        dev["x"], dev["labels"], dev["weights"],
                        dev["mask"], dev["offsets"], dev["w0"],
                    )
                    # Device cost of bucket b's chunk-train program
                    # (once per session per bucket shape; the program
                    # just dispatched, so the relower is cache-warm).
                    _device.maybe_capture(
                        f"re_chunk_train.b{b}", _re_chunk_train,
                        (opt.optimizer, opt.config, has_l1,
                         opt.objective, dev["x"], dev["labels"],
                         dev["weights"], dev["mask"], dev["offsets"],
                         dev["w0"]), span="chunk_compute")
                    if pending is not None:
                        # Lag-1 harvest IS the dispatch backpressure:
                        # fetching chunk j-1's blocks fences its solve
                        # while chunk j computes and chunks j+1..
                        # prefetch — at most two chunks' device buffers
                        # are ever in flight.
                        harvest(*pending)
                pending = (out, b, ents, ex, rows, cols)
                # Live entity-chunk progress (ISSUE 10): within-sweep
                # ETA from the observed chunk rate; no-op when off.
                _mon.progress(f"re.{self.name}", ci + 1, len(specs),
                              unit="chunks")
            if pending is not None:
                harvest(*pending)
        telemetry.count("re.sweeps")
        telemetry.count("re.chunks_streamed", len(specs))

        # Retirement candidates: solved, lane-converged, coefficients
        # AND offsets both moved < tolerance this sweep.  Committed by
        # the CD loop's retire_converged() hook, so direct train()
        # callers (parity tests, notebooks) see pure streaming.
        if self.retirement and self._prev_offsets is not None:
            drift_prev = np.abs(off - self._prev_offsets)
            for b in range(len(self._pending)):
                doff = self._entity_max(b, drift_prev)
                self._pending[b] = (solved[b] & conv[b]
                                    & (dw[b] < rtol) & (doff < rtol))
        self._prev_offsets = off.copy()

        # The sweep churned one staging chunk's arrays per packed chunk;
        # glibc retains much of that as arena slack, which would read as
        # permanent RSS — the exact number an out-of-core path exists to
        # bound.  Once per sweep, return it (no-op off Linux).
        from photon_ml_tpu.data.chunk_store import release_free_heap

        release_free_heap()
        blocks_out = [jnp.asarray(w) for w in self._w_host]
        self._last_w_blocks = list(blocks_out)
        self._cached_scores = jnp.asarray(self._scores_host)
        n_solved = int(sum(m.sum() for m in solved))
        telemetry.count("re.entities_solved", n_solved)
        diag = {
            "entities": int(sum(ne)),
            "entities_solved": n_solved,
            "entities_converged": int(sum((m & c).sum()
                                          for m, c in zip(solved, conv))),
            "entities_retired": retired_now,
            "entities_woken": woken,
            "max_solver_iterations": max_iters,
            "chunks_streamed": len(specs),
        }
        self.last_diag = diag
        # Per-sweep retirement/convergence dynamics event (ISSUE 8) —
        # the trajectory the retirement machinery is judged on, not
        # just end-state parity.
        _conv.re_sweep(self.name, diag)
        return blocks_out, diag

    # -- checkpoint/resume (ISSUE 9) -----------------------------------------

    def runtime_state(self) -> dict:
        """Checkpoint tree of everything the retirement machinery
        carries BETWEEN sweeps: resident coefficient blocks,
        active/pending masks, the score plane, and the offset baselines
        the wake/retire decisions compare against.  Captured by the CD
        loop's checkpointer so a resumed run retires/wakes exactly as
        the uninterrupted run would have."""
        return {
            "w_host": [np.asarray(w) for w in self._w_host],
            "active": [np.asarray(a) for a in self._active],
            "pending": [np.asarray(p) for p in self._pending],
            "scores_host": np.asarray(self._scores_host),
            "solved_offsets": (None if self._solved_offsets is None
                               else np.asarray(self._solved_offsets)),
            "prev_offsets": (None if self._prev_offsets is None
                             else np.asarray(self._prev_offsets)),
        }

    def restore_runtime_state(self, state: dict):
        """Inverse of ``runtime_state``.  Returns (canonical
        coefficient blocks, cached score plane): the CD loop installs
        the RETURNED blocks as the warm start, so ``train``'s identity
        check recognizes them and keeps the restored retirement
        bookkeeping instead of resetting it (``_adopt_warm_start``
        exists for FOREIGN warm starts, and a checkpoint is not
        foreign)."""
        for b, w in enumerate(state["w_host"]):
            wb = np.asarray(w, np.float32)
            if wb.shape != self._w_host[b].shape:
                raise ValueError(
                    f"checkpoint bucket {b} shape {wb.shape} != "
                    f"{self._w_host[b].shape} (grouping changed; a "
                    "checkpoint only resumes its own dataset/config)")
            self._w_host[b] = wb.copy()
            self._active[b] = np.asarray(state["active"][b], bool).copy()
            self._pending[b] = np.asarray(state["pending"][b],
                                          bool).copy()
        self._scores_host = np.asarray(state["scores_host"],
                                       np.float32).copy()
        self._solved_offsets = (
            None if state.get("solved_offsets") is None
            else np.asarray(state["solved_offsets"], np.float32).copy())
        self._prev_offsets = (
            None if state.get("prev_offsets") is None
            else np.asarray(state["prev_offsets"], np.float32).copy())
        blocks = [jnp.asarray(w) for w in self._w_host]
        self._last_w_blocks = list(blocks)
        self._cached_scores = jnp.asarray(self._scores_host)
        return blocks, self._cached_scores

    def retire_converged(self) -> int:
        """Commit this sweep's retirement candidates (the coordinate-
        descent hook, called between sweeps).  Returns the number of
        newly retired entities; a no-op (0) with retirement off."""
        if not self.retirement:
            return 0
        newly = 0
        for b in range(len(self._active)):
            pend = self._pending[b] & self._active[b]
            newly += int(pend.sum())
            self._active[b] &= ~pend
            self._pending[b][:] = False
        if newly:
            # Commit-time event: re_sweep samples retirement as of
            # sweep START, so the last sweep's commit lands here.
            _conv.re_retirement(self.name, newly, self.entities_retired)
        return newly

    # -- score / export / variances -----------------------------------------

    def score(self, coefficient_blocks: list[Array]) -> Array:
        """Raw x·w per example.  The blocks the last ``train`` returned
        hit the cached plane (scores were computed inside the solve
        dispatch); zero blocks short-circuit (the CD shape probe);
        anything else streams one scoring pass over the store."""
        if (self._cached_scores is not None
                and self._is_last_train_output(list(coefficient_blocks))):
            return self._cached_scores
        if all(not bool(jnp.any(bk != 0)) for bk in coefficient_blocks):
            return jnp.zeros((self.n_examples,), jnp.float32)
        blocks = [np.asarray(bk, np.float32) for bk in coefficient_blocks]
        scores = np.zeros(self.n_examples, np.float32)
        zeros = np.zeros(0, np.float32)   # unused: x_only skips offsets
        for j, item in self._stream(self._full_specs(), zeros,
                                    with_w0=False, x_only=True):
            dev, b, ents, ex, rows, cols = item
            w_chunk = np.zeros((self.chunk_ents[b], self.widths[b]),
                               np.float32)
            w_chunk[: len(ents)] = blocks[b][ents]
            blk = np.asarray(_re_chunk_score(dev["x"],
                                             jnp.asarray(w_chunk)))
            scores[ex] = blk[rows, cols]
        return jnp.asarray(scores)

    def _full_specs(self) -> list[tuple[int, np.ndarray]]:
        specs = []
        for b, e in enumerate(self.grouping.n_entities):
            C = self.chunk_ents[b]
            for s in range(self.n_source_chunks[b]):
                lo = s * C
                specs.append((b, np.arange(lo, min(lo + C, e),
                                           dtype=np.int64)))
        return specs

    def as_model(self, coefficient_blocks: list[Array]) -> RandomEffectModel:
        return RandomEffectModel(
            coefficient_blocks=coefficient_blocks,
            grouping=self.grouping,
            feature_shard=self.name,
            projection=self.projection,
        )

    def compute_variance_blocks(
        self, coefficient_blocks: list[Array], offsets: Array
    ) -> list[Array]:
        """SIMPLE per-entity variances, streamed chunk-by-chunk (one
        more full pass over the store — variances are a once-per-fit
        export, not sweep state)."""
        off = np.asarray(offsets, np.float32)
        blocks = [np.asarray(bk, np.float32) for bk in coefficient_blocks]
        out = [np.zeros((e, p), np.float32)
               for e, p in zip(self.grouping.n_entities, self.widths)]
        for j, item in self._stream(self._full_specs(), off,
                                    with_w0=False):
            dev, b, ents, ex, rows, cols = item
            w_chunk = np.zeros((self.chunk_ents[b], self.widths[b]),
                               np.float32)
            w_chunk[: len(ents)] = blocks[b][ents]
            v = np.asarray(_re_chunk_vars(
                self.problem.objective, dev["x"], dev["labels"],
                dev["weights"], dev["mask"], dev["offsets"],
                jnp.asarray(w_chunk)))
            out[b][ents] = v[: len(ents)]
        return [jnp.asarray(v) for v in out]


def _shard_re_blocks(coord_kwargs: dict, mesh) -> dict:
    """Entity-shard a coordinate's bucket blocks on the mesh
    (reference parallelism strategy #2 — per-entity solves are
    communication-free, so the leading entity axis shards cleanly)."""
    if mesh is None:
        return coord_kwargs
    from photon_ml_tpu.parallel.mesh import shard_entity_blocks

    for key in ("x_blocks", "label_blocks", "weight_blocks", "mask_blocks"):
        coord_kwargs[key] = shard_entity_blocks(coord_kwargs[key], mesh)
    return coord_kwargs


def build_random_effect_coordinate(
    name: str,
    dataset: GameDataset,
    feature_shard: str,
    objective: GLMObjective,
    config: OptimizerConfig | None = None,
    optimizer=None,
    bucket_base: int = 4,
    mesh=None,
) -> RandomEffectCoordinate:
    """Host ETL → device blocks: the reference's partition-and-group
    pipeline (``RandomEffectDataset.apply``) as one deterministic pass."""
    from photon_ml_tpu.optim.base import OptimizerType

    x = np.asarray(dataset.features[feature_shard], np.float32)
    labels = dataset.labels.astype(np.float32)
    with telemetry.stage("group_entities", entity_key=name,
                         rows=len(labels)) as stage:
        grouping = group_by_entity(dataset.entity_ids[name],
                                   bucket_base=bucket_base)
        scalar_blocks = _scalar_blocks(grouping, labels,
                                       dataset.weight_array())
        index_maps = _index_maps(grouping)
        x_blocks = []
        for b, (cap, ne) in enumerate(zip(grouping.capacities,
                                          grouping.n_entities)):
            sel = np.where(grouping.example_bucket == b)[0]
            xb = np.zeros((ne, cap, x.shape[1]), np.float32)
            xb[grouping.example_row[sel], grouping.example_col[sel]] = x[sel]
            x_blocks.append(xb)
        stage.set(**_grouping_counts(grouping))

    problem = OptimizationProblem(
        objective=objective,
        optimizer=optimizer or OptimizerType.LBFGS,
        config=config or OptimizerConfig(),
    )
    _log_occupancy(name, grouping)
    return RandomEffectCoordinate(
        name=name,
        grouping=grouping,
        n_examples=len(labels),
        problem=problem,
        **_place_re(name, x_blocks, scalar_blocks, index_maps, mesh),
    )


def _grouping_counts(grouping) -> dict:
    """What a ``group_entities`` stage says of its result."""
    slots = sum(cap * ne for cap, ne in zip(grouping.capacities,
                                            grouping.n_entities))
    return {"entities": len(grouping.entity_ids),
            "buckets": len(grouping.capacities),
            "padded_slots": int(slots),
            "real_rows": int(grouping.n_examples)}


def _place_re(name: str, x_blocks, scalar_blocks, index_maps, mesh) -> dict:
    """Host blocks → the device arrays of a ``RandomEffectCoordinate``
    (entity-sharded on ``mesh``).  The stage times the enqueue: the
    transfers drain under whatever the host does next."""
    lab_blocks, wt_blocks, mask_blocks = scalar_blocks
    placed = sum(a.nbytes for group in (x_blocks, *scalar_blocks,
                                        *index_maps) for a in group)
    with telemetry.stage("place_re", entity_key=name, bytes=int(placed)):
        blocks = _shard_re_blocks(
            dict(x_blocks=[jnp.asarray(b) for b in x_blocks],
                 label_blocks=[jnp.asarray(b) for b in lab_blocks],
                 weight_blocks=[jnp.asarray(b) for b in wt_blocks],
                 mask_blocks=[jnp.asarray(b) for b in mask_blocks]),
            mesh,
        )
        ex_idx, row_idx, col_idx = (
            [jnp.asarray(a) for a in maps] for maps in index_maps)
    return dict(blocks, ex_idx=ex_idx, row_idx=row_idx, col_idx=col_idx)


def _log_occupancy(name: str, grouping) -> None:
    """One line of bucket occupancy / padding-waste stats per RE
    coordinate build (ISSUE 5 satellite): a ``bucket_base`` regression
    multiplies every block array silently — make it visible."""
    from photon_ml_tpu.game.dataset import bucket_occupancy

    occ = bucket_occupancy(grouping)
    per_bucket = ", ".join(
        f"cap={b['capacity']}:E={b['entities']}:fill={b['fill_fraction']}"
        for b in occ["buckets"])
    logger.info(
        "RE coordinate '%s': %d entities / %d examples in %d buckets "
        "[%s]; padded-slot ratio %.4f (%d of %d slots)",
        name, occ["entities"], occ["examples"], len(occ["buckets"]),
        per_bucket, occ["padded_slot_ratio"], occ["padded_slots"],
        occ["total_slots"])


def _scalar_blocks(grouping, labels, weights):
    """labels/weights/mask → per-bucket [E_b, cap_b] host blocks."""
    lab_blocks, wt_blocks, mask_blocks = [], [], []
    for b, (cap, ne) in enumerate(zip(grouping.capacities,
                                      grouping.n_entities)):
        sel = np.where(grouping.example_bucket == b)[0]
        rows = grouping.example_row[sel]
        cols = grouping.example_col[sel]
        lb = np.zeros((ne, cap), np.float32)
        wb = np.zeros((ne, cap), np.float32)
        mb = np.zeros((ne, cap), np.float32)
        lb[rows, cols] = labels[sel]
        wb[rows, cols] = weights[sel]
        mb[rows, cols] = 1.0
        lab_blocks.append(lb)
        wt_blocks.append(wb)
        mask_blocks.append(mb)
    return lab_blocks, wt_blocks, mask_blocks


def _index_maps(grouping):
    """Per-bucket (example, entity slot, within-entity position) int32
    index arrays, on the host."""
    ex_idx, row_idx, col_idx = [], [], []
    for b in range(len(grouping.capacities)):
        sel = np.where(grouping.example_bucket == b)[0]
        ex_idx.append(sel.astype(np.int32))
        row_idx.append(grouping.example_row[sel].astype(np.int32))
        col_idx.append(grouping.example_col[sel].astype(np.int32))
    return ex_idx, row_idx, col_idx


def build_random_effect_coordinate_sparse(
    name: str,
    dataset: GameDataset,
    feature_shard: str,
    objective: GLMObjective,
    global_dim: int,
    config: OptimizerConfig | None = None,
    optimizer=None,
    bucket_base: int = 4,
    mesh=None,
) -> RandomEffectCoordinate:
    """Sparse-shard variant: features arrive as per-example (col_ids,
    values) rows in a wide global space; each entity's problem is solved
    in its observed-feature subspace (reference
    ``LinearSubspaceProjector`` path, SURVEY §2.4)."""
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.game.projector import build_subspace_projection
    from photon_ml_tpu.optim.base import OptimizerType

    rows = SparseRows.from_rows(dataset.features[feature_shard])
    labels = dataset.labels.astype(np.float32)
    with telemetry.stage("group_entities", entity_key=name,
                         rows=len(labels)) as stage:
        grouping = group_by_entity(dataset.entity_ids[name],
                                   bucket_base=bucket_base)
        with telemetry.stage("re_project", entity_key=name,
                             nnz=int(rows.nnz)) as project_stage:
            projection, x_blocks = build_subspace_projection(
                grouping, rows, global_dim
            )
            project_stage.set(**projection.counts(grouping),
                              bytes=sum(b.nbytes for b in x_blocks))
        scalar_blocks = _scalar_blocks(grouping, labels,
                                       dataset.weight_array())
        index_maps = _index_maps(grouping)
        stage.set(**_grouping_counts(grouping))

    problem = OptimizationProblem(
        objective=objective,
        optimizer=optimizer or OptimizerType.LBFGS,
        config=config or OptimizerConfig(),
    )
    _log_occupancy(name, grouping)
    return RandomEffectCoordinate(
        name=name,
        grouping=grouping,
        n_examples=len(labels),
        problem=problem,
        projection=projection,
        **_place_re(name, x_blocks, scalar_blocks, index_maps, mesh),
    )


def build_streamed_random_effect_coordinate(
    name: str,
    dataset: GameDataset,
    feature_shard: str,
    objective: GLMObjective,
    spill_dir: str,
    chunk_entities: int,
    config: OptimizerConfig | None = None,
    optimizer=None,
    bucket_base: int = 4,
    host_max_resident: int = 2,
    prefetch_depth: int = 2,
    retirement: bool = True,
    mesh=None,
) -> StreamedRandomEffectCoordinate:
    """Out-of-core variant of the RE coordinate builders: entity
    blocks are built ONE CHUNK AT A TIME and spilled straight to the
    chunk store (content-keyed; an existing file for the same data +
    config is reused, so a second run's build is pure stat calls), so
    peak host RSS during ETL is bounded by the chunk, not by E.

    Dense feature shards assemble each chunk directly from the
    per-example feature rows; sparse shards go through the subspace
    projection (``game.projector``) first — the projection build is
    inherently global (per-entity column sets), so its blocks are
    materialized once, spilled, and freed, with lineage rebuild
    re-running the (deterministic) projection on demand.

    ``chunk_entities`` is rounded up to the mesh grid when ``mesh`` is
    given: every packed chunk then entity-shards evenly
    (``parallel.mesh.place_entity_chunk``).
    """
    from photon_ml_tpu.data.chunk_store import (
        ENTITY_CHUNK_CODEC,
        ChunkStore,
        array_content_key,
        release_free_heap,
    )
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.optim.base import OptimizerType

    if chunk_entities <= 0:
        raise ValueError("chunk_entities must be positive")
    if not spill_dir:
        raise ValueError(
            "streamed random-effect training requires spill_dir (the "
            "chunk store is the architecture, not an option)")
    feats = dataset.features[feature_shard]
    entity_ids = np.asarray(dataset.entity_ids[name])
    grouping = group_by_entity(entity_ids, bucket_base=bucket_base)
    labels = dataset.labels.astype(np.float32)
    weights = dataset.weight_array()
    n_dev = 1 if mesh is None else mesh.devices.size
    # Per-bucket chunk size: the requested budget, balanced across the
    # bucket's chunk count and capped by its entity count — a GLOBAL
    # chunk size would pad a small bucket's one chunk with dead solve
    # lanes (at cap 1024 × p that is real FLOPs and real transfer) —
    # then rounded up to the mesh grid.
    chunk_ents = []
    for e in grouping.n_entities:
        k_b = max(1, -(-e // max(1, int(chunk_entities))))
        cb = -(-e // k_b)
        chunk_ents.append(-(-cb // n_dev) * n_dev)
    ex_sorted, ent_starts = _example_runs(grouping)

    sparse = not isinstance(feats, np.ndarray)
    projection = None
    if sparse:
        from photon_ml_tpu.game.projector import build_subspace_projection

        if not isinstance(feats, SparseRows):
            feats = SparseRows.from_rows(feats)
        global_dim = dataset.feature_dim(feature_shard)
        projection, x_blocks_np = build_subspace_projection(
            grouping, feats, global_dim)
        widths = [xb.shape[-1] for xb in x_blocks_np]
        # Blocks are freed after the spill below; lineage rebuild
        # re-runs the (deterministic) projection on demand.
        src_holder = {"blocks": x_blocks_np}

        def chunk_x(b, lo, hi):
            if src_holder["blocks"] is None:
                src_holder["blocks"] = build_subspace_projection(
                    grouping, feats, global_dim)[1]
            return src_holder["blocks"][b][lo:hi]

        key_arrays = [np.asarray(feats.indptr), np.asarray(feats.cols),
                      np.asarray(feats.vals, np.float32), labels,
                      weights, entity_ids]
    else:
        x = np.asarray(feats, np.float32)
        widths = [x.shape[1]] * len(grouping.capacities)
        src_holder = None
        chunk_x = None
        key_arrays = [x, labels, weights, entity_ids]

    n_source_chunks = [-(-e // cb)
                       for e, cb in zip(grouping.n_entities, chunk_ents)]
    chunk_base = list(np.concatenate(
        [[0], np.cumsum(n_source_chunks)[:-1]]).astype(int)) \
        if n_source_chunks else []
    total_chunks = int(sum(n_source_chunks))

    def locate(gid: int) -> tuple[int, int]:
        for b in range(len(chunk_base) - 1, -1, -1):
            if gid >= chunk_base[b]:
                return b, gid - chunk_base[b]
        raise IndexError(gid)

    def build_chunk(b: int, s: int) -> dict:
        cap = grouping.capacities[b]
        p = widths[b]
        C = chunk_ents[b]
        lo = s * C
        hi = min(lo + C, grouping.n_entities[b])
        ents = np.arange(lo, hi, dtype=np.int64)
        ex, rows, cols = _entity_example_runs(
            ex_sorted[b], ent_starts[b], ents)
        lb = np.zeros((C, cap), np.float32)
        wt = np.zeros((C, cap), np.float32)
        mk = np.zeros((C, cap), np.float32)
        lb[rows, cols] = labels[ex]
        wt[rows, cols] = weights[ex]
        mk[rows, cols] = 1.0
        xc = np.zeros((C, cap, p), np.float32)
        if sparse:
            xc[: hi - lo] = chunk_x(b, lo, hi)
        else:
            xc[rows, cols] = x[ex]
        return {"x": xc, "labels": lb, "weights": wt, "mask": mk}

    def rebuild(gid: int) -> dict:
        b, s = locate(gid)
        return build_chunk(b, s)

    key = array_content_key(key_arrays, {
        "kind": "re-sparse" if sparse else "re-dense",
        "chunk_ents": [int(cb) for cb in chunk_ents],
        "bucket_base": int(bucket_base),
        "widths": [int(p) for p in widths],
    })
    store = ChunkStore(spill_dir, key, total_chunks,
                       host_max_resident=host_max_resident,
                       rebuild=rebuild, codec=ENTITY_CHUNK_CODEC)
    missing = [gid for gid in range(total_chunks) if not store.has(gid)]
    for gid in missing:
        b, s = locate(gid)
        # Default admission (the first window's worth stays resident):
        # the first sweep visits chunks in exactly this order, so it
        # starts warm.
        store.put(gid, build_chunk(b, s))
    if sparse:
        src_holder["blocks"] = None   # spilled; lineage rebuilds
    if missing:
        release_free_heap()   # build churn must not read as steady RSS

    problem = OptimizationProblem(
        objective=objective,
        optimizer=optimizer or OptimizerType.LBFGS,
        config=config or OptimizerConfig(),
    )
    _log_occupancy(name, grouping)
    logger.info(
        "streamed RE coordinate '%s': %d entity chunks (per-bucket "
        "sizes %s; %d built, %d reused; host window %d) spilled to %s",
        name, total_chunks, chunk_ents, len(missing),
        total_chunks - len(missing), store.host_max_resident, spill_dir)
    return StreamedRandomEffectCoordinate(
        name=name,
        grouping=grouping,
        problem=problem,
        store=store,
        chunk_ents=[int(cb) for cb in chunk_ents],
        widths=[int(p) for p in widths],
        ex_sorted=ex_sorted,
        ent_starts=ent_starts,
        chunk_base=[int(cb) for cb in chunk_base],
        n_source_chunks=[int(ks) for ks in n_source_chunks],
        n_examples=len(labels),
        mesh=mesh,
        prefetch_depth=prefetch_depth,
        retirement=retirement,
        projection=projection,
    )
