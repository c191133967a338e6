"""Gaussian prior toward a previous model: incremental-training loss.

Reference counterparts: ``PriorDistribution`` /
``PriorDistributionDiff`` mixins on the loss functions (photon-lib/api
``com.linkedin.photon.ml.function`` [expected paths, mount unavailable —
see SURVEY.md §2.2]): when warm-start training is given a prior model
with coefficient means AND variances, the new fit is regularized toward
the old coefficients with per-coordinate strength 1/σ²— Bayesian
incremental training — instead of (or on top of) plain L2 toward zero.

The penalty added to the objective is

    0.5 · λ_prior · Σ_j (w_j − μ_j)² / σ_j²

with derivatives λ_prior·(w−μ)/σ² (gradient), λ_prior·v/σ² (HVP) and
λ_prior/σ² (Hessian diagonal) — a diagonal quadratic, so it fuses into
the same device program as the data term.  It is added to the L2 term,
not put in its place (the reference's choice is not verified here).

``GaussianPrior`` is the fixed effect's prior, one [dim] pair.  A random
effect's is ``EntityPriors``: one such prior per entity, held as blocks
shaped like the coordinate's coefficient blocks ([E_b, p_b] a bucket)
and sliced with them into the vmapped per-entity solves
(``game.coordinates``), each lane's ``GaussianPrior`` of [p_b].  A
coefficient without a prior (an entity or a column the previous model
did not have) has precision 0 there: its term is exactly zero.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

Array = jax.Array

# The smallest variance a prior takes from a saved model: precision at
# most 1e12.
MIN_VARIANCE = 1e-12


@struct.dataclass
class GaussianPrior:
    """Diagonal Gaussian prior N(means, diag(variances)) on coefficients."""

    means: Array        # [dim]
    precisions: Array   # [dim] = 1/σ²  (precomputed; σ²>0 enforced upstream)
    weight: Array       # scalar λ_prior (reference incremental weight)

    @staticmethod
    def from_model(
        means: Array, variances: Array, weight: float = 1.0,
        min_variance: float = MIN_VARIANCE,
    ) -> "GaussianPrior":
        v = jnp.maximum(jnp.asarray(variances, jnp.float32), min_variance)
        return GaussianPrior(
            means=jnp.asarray(means, jnp.float32),
            precisions=1.0 / v,
            weight=jnp.asarray(weight, jnp.float32),
        )

    def value(self, w: Array) -> Array:
        d = w - self.means
        return 0.5 * self.weight * jnp.vdot(d, self.precisions * d)

    def gradient(self, w: Array) -> Array:
        return self.weight * self.precisions * (w - self.means)

    def hessian_vector(self, v: Array) -> Array:
        return self.weight * self.precisions * v

    def hessian_diagonal(self) -> Array:
        return self.weight * self.precisions


@struct.dataclass
class EntityPriors:
    """Per-entity diagonal Gaussian priors of a random effect: one
    [E_b, p_b] block of means and one of precisions (1/σ², 0 where a
    coefficient has none) for each bucket, shaped as its coefficient
    blocks, and the one scalar weight λ_prior."""

    means: list        # [E_b, p_b] per bucket
    precisions: list   # [E_b, p_b] per bucket
    weight: Array      # scalar λ_prior

    def entity(self, means: Array, precisions: Array) -> GaussianPrior:
        """One entity's prior, from its rows of a bucket's blocks."""
        return GaussianPrior(means=means, precisions=precisions,
                             weight=self.weight)
