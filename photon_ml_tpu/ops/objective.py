"""The GLM objective: fused value / gradient / Hessian-vector over a batch.

Reference counterparts (all [expected paths, mount unavailable — SURVEY.md]):
- ``ObjectiveFunction`` / ``DiffFunction`` / ``TwiceDiffFunction`` traits
  (photon-lib ``com.linkedin.photon.ml.function``),
- ``SingleNodeGLMLossFunction`` and the hot-loop aggregators
  ``ValueAndGradientAggregator`` / ``HessianVectorAggregator`` /
  ``HessianDiagonalAggregator`` (``...function.glm``).

Where the reference folds example-by-example in Scala, this objective is a
handful of fused array ops (margin contraction → elementwise loss → masked
reduce / transposed contraction), which XLA compiles onto the MXU/VPU as
one pipeline with no intermediate HBM round-trips.  The *distributed*
variant (reference ``DistributedGLMLossFunction`` + treeAggregate) is this
same objective wrapped in ``shard_map`` + ``psum`` — see
``photon_ml_tpu.parallel.distributed_objective``.

Everything is a pure function of ``(w, batch)`` so the same objective is
- jitted for the fixed-effect solve,
- vmapped over entity blocks for random-effect solves,
- shard_mapped over the device mesh for data parallelism.

The algebra is stated once, split at the margins.  A GLM's margins are
affine in w, ``margins(w) = X·w + o`` with linear part ``margin_step(d) =
X·d``, and everything else (value, gradient, curvature) is a function of
given margins and w: ``value_from_margins``,
``value_and_gradient_from_margins``.  ``value``, ``value_and_gradient``
and ``hessian_vector`` are compositions of the two sides, and
``optim.problem.as_margin_split`` hands the sides to a solver as plain
functions (``optim.base.MarginSplit``): a line search that knows
``margins(w + a·d) = margins(w) + a·X·d`` contracts X once an iteration
instead of once a trial (``optim.lbfgs``).

Sign/weight conventions follow the reference: total value =
Σ_i weight_i·ℓ(margin_i, y_i) + ½·λ₂·‖w‖² (unnormalized by n; L1 handled by
OWL-QN, not here).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.data.batch import Batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.prior import GaussianPrior
from photon_ml_tpu.ops.regularization import RegularizationContext

Array = jax.Array


@struct.dataclass
class GLMObjective:
    """Bundle of (loss, regularization, normalization) over a batch.

    The batch is passed per-call (not stored) so one objective instance can
    serve many shards / entity blocks, and so batches can be donated.
    ``loss`` is static (hashable) metadata; reg/norm are pytrees of scalars
    and [dim] vectors that trace cleanly.
    """

    loss: PointwiseLoss = struct.field(pytree_node=False)
    reg: RegularizationContext
    norm: NormalizationContext
    # Optional Gaussian prior toward a previous model's coefficients
    # (incremental training, reference PriorDistribution — see ops/prior.py).
    prior: "GaussianPrior | None" = None

    # ---- the split at the margins ------------------------------------------
    # The objective is a GLM: f(w) = Σ wl·ℓ(m(w), y) + R(w), with margins
    # m(w) = X·w + o affine in w.  Everything below is written once, on
    # the two sides of m: the map into margins (and its linear part),
    # and the value / gradient / curvature from given margins.

    def _x_dot(self, v: Array, batch: Batch, offsets: Array | None) -> Array:
        m = batch.x_dot(self.norm.model_to_raw(v))
        if offsets is not None:
            m = m + offsets
        if not self.norm.is_identity:
            m = m - self.norm.margin_correction(v)
        return m

    def margins(self, w: Array, batch: Batch) -> Array:
        """w → X·w + o, the affine map into the margins [n] (under
        normalization the factors fold into w and the shifts become a
        scalar)."""
        return self._x_dot(w, batch, batch.offsets)

    def margin_step(self, d: Array, batch: Batch) -> Array:
        """d → X·d, the linear part of ``margins``:
        ``margins(w + a·d) = margins(w) + a·margin_step(d)``."""
        return self._x_dot(d, batch, None)

    def _residual_to_grad(self, r: Array, batch: Batch) -> Array:
        """r (already masked+weighted, [n]) → model-space gradient [dim]."""
        g_raw = batch.xt_dot(r)
        return self.norm.grad_to_model(g_raw, jnp.sum(r))

    def value_from_margins(self, m: Array, w: Array, batch: Batch) -> Array:
        """f(w) given ``m = margins(w)``: no contraction with X."""
        wl = batch.weights * batch.mask
        data_val = jnp.sum(wl * self.loss.loss(m, batch.labels))
        val = data_val + self.reg.l2_value(w)
        if self.prior is not None:
            val = val + self.prior.value(w)
        return val

    def value_and_gradient_from_margins(
        self, m: Array, w: Array, batch: Batch
    ) -> tuple[Array, Array]:
        """(f(w), ∇f(w)) given ``m = margins(w)``: one Xᵀr."""
        wl = batch.weights * batch.mask
        val = jnp.sum(wl * self.loss.loss(m, batch.labels)) + self.reg.l2_value(w)
        r = wl * self.loss.d1(m, batch.labels)
        grad = self._residual_to_grad(r, batch) + self.reg.l2_gradient(w)
        if self.prior is not None:
            val = val + self.prior.value(w)
            grad = grad + self.prior.gradient(w)
        return val, grad

    # ---- TwiceDiffFunction surface ---------------------------------------

    def value(self, w: Array, batch: Batch) -> Array:
        return self.value_from_margins(self.margins(w, batch), w, batch)

    def value_and_gradient(self, w: Array, batch: Batch) -> tuple[Array, Array]:
        """The hot path: one fused pass for (value, gradient)."""
        return self.value_and_gradient_from_margins(
            self.margins(w, batch), w, batch)

    def gradient(self, w: Array, batch: Batch) -> Array:
        return self.value_and_gradient(w, batch)[1]

    def hessian_vector(self, w: Array, v: Array, batch: Batch) -> Array:
        """Gauss–Newton/true HVP: X^T diag(wl·d2) X v  (+ λ₂ v).

        Under normalization, (Xv) uses the same margin algebra as the
        forward pass (factors fold into v, shifts become a scalar).
        """
        m = self.margins(w, batch)
        wl = batch.weights * batch.mask
        d2 = wl * self.loss.d2(m, batch.labels)
        r = d2 * self.margin_step(v, batch)
        out = self._residual_to_grad(r, batch) + self.reg.l2_hessian_vector(v)
        if self.prior is not None:
            out = out + self.prior.hessian_vector(v)
        return out

    def hessian_diagonal(self, w: Array, batch: Batch) -> Array:
        """diag(X^T diag(wl·d2) X) + λ₂ — for SIMPLE variance computation.

        Reference: ``HessianDiagonalAggregator``.  Exact for identity and
        factor-only normalization; with shifts the cross-terms are included
        via the expanded square (x_j − s_j)² = x_j² − 2·s_j·x_j + s_j².
        """
        m = self.margins(w, batch)
        wl = batch.weights * batch.mask
        d2 = wl * self.loss.d2(m, batch.labels)

        prior_diag = (self.prior.hessian_diagonal()
                      if self.prior is not None else 0.0)
        sq_batch = _elementwise_square_batch(batch)
        diag_raw = sq_batch.xt_dot(d2)          # Σ_i d2_i · x_ij²
        if self.norm.is_identity:
            return diag_raw + self.reg.l2_hessian_diagonal(w) + prior_diag

        f = (
            self.norm.factors
            if self.norm.factors is not None
            else jnp.ones_like(w)
        )
        diag = diag_raw * f * f
        if self.norm.shifts is not None:
            s = self.norm.shifts
            cross = batch.xt_dot(d2)            # Σ_i d2_i · x_ij
            total = jnp.sum(d2)                 # Σ_i d2_i
            diag = diag - 2.0 * f * f * s * cross + f * f * s * s * total
        return diag + self.reg.l2_hessian_diagonal(w) + prior_diag

    # ---- conveniences -----------------------------------------------------

    def predict_margins(self, w: Array, batch: Batch) -> Array:
        return self.margins(w, batch)

    def predict_means(self, w: Array, batch: Batch) -> Array:
        return self.loss.mean(self.margins(w, batch))


def _elementwise_square_batch(batch: Batch) -> Batch:
    """Batch with x_ij → x_ij² (same sparsity), for diagonal aggregation."""
    from photon_ml_tpu.data.batch import DenseBatch, SparseBatch

    if isinstance(batch, DenseBatch):
        return batch.replace(x=batch.x * batch.x)
    assert isinstance(batch, SparseBatch)
    cm = batch.colmajor.squared() if batch.colmajor is not None else None
    pair = batch.grr.squared() if batch.grr is not None else None
    return batch.replace(values=batch.values * batch.values, colmajor=cm,
                         grr=pair)


class ObjectiveFns(NamedTuple):
    """Plain-function view (for optimizers that take callables)."""

    value_and_grad: callable
    hvp: callable


def as_fns(obj: GLMObjective, batch: Batch) -> ObjectiveFns:
    return ObjectiveFns(
        value_and_grad=lambda w: obj.value_and_gradient(w, batch),
        hvp=lambda w, v: obj.hessian_vector(w, v, batch),
    )


# ---------------------------------------------------------------------------
# Swept (stacked-coefficient) surface: evaluate L λ-lanes against ONE
# shared batch.  The λ grid's dominant cost is moving the batch through
# the memory system (GRR plans stream at ~30% of HBM roofline; the
# chunked regime pays 6.5 s per full-data pass — PERF.md), so the sweep
# evaluates W [L, dim] with ``vmap(in_axes=(0, None))``: the batch is
# read once and every lane contracts against it.  Per-lane L2 weight
# rides as a [L] array (λ is a traced leaf, so one compiled program
# covers any grid).  GRR-plan batches get a ``lax.map`` lane loop
# instead — the Mosaic kernel has no batching rule, and the data is
# already resident so the loop still reads it from HBM, not the host.
# ---------------------------------------------------------------------------


def _lane_objective(obj: GLMObjective, l2_weight: Array) -> GLMObjective:
    """``obj`` with one lane's (traced scalar) L2 weight installed.

    Only the smooth L2 part varies inside a swept evaluation; per-lane
    L1 is the optimizer's business (OWL-QN), exactly as in the
    single-lane convention (module docstring).
    """
    return obj.replace(reg=obj.reg.replace(l2_weight=l2_weight))


def sweep_value_and_gradient(
    obj: GLMObjective, W: Array, batch: Batch,
    l2_weights: Array | None = None, use_map: bool = False,
) -> tuple[Array, Array]:
    """(W [L, dim], shared batch) → (values [L], gradients [L, dim]).

    ``l2_weights`` [L] installs a per-lane L2 weight (None keeps the
    objective's own, shared across lanes — the chunked inner sweep,
    whose reg is added outside the chunk loop).  ``use_map`` switches
    the lane axis from ``vmap`` to a ``lax.map`` loop (GRR plans /
    shard_mapped objectives, which have no batching rule).
    """
    if l2_weights is None:
        fn = lambda w: obj.value_and_gradient(w, batch)
        xs = W
    else:
        fn = lambda args: _lane_objective(obj, args[1]).value_and_gradient(
            args[0], batch)
        xs = (W, l2_weights)
    if use_map:
        return jax.lax.map(fn, xs)
    return jax.vmap(fn)(xs)


def sweep_value(
    obj: GLMObjective, W: Array, batch: Batch,
    l2_weights: Array | None = None, use_map: bool = False,
) -> Array:
    """Value-only lane sweep (line-search trials): W [L, dim] → [L]."""
    if l2_weights is None:
        fn = lambda w: obj.value(w, batch)
        xs = W
    else:
        fn = lambda args: _lane_objective(obj, args[1]).value(args[0], batch)
        xs = (W, l2_weights)
    if use_map:
        return jax.lax.map(fn, xs)
    return jax.vmap(fn)(xs)
