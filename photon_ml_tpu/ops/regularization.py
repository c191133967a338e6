"""Regularization contexts (L1 / L2 / elastic net).

Reference counterpart: ``RegularizationContext`` /
``ElasticNetRegularizationContext`` / ``RegularizationType``
(photon-lib ``com.linkedin.photon.ml.optimization`` [expected path, mount
unavailable — see SURVEY.md]).

Semantics mirror the reference:

- the **L2 part** is smooth and folded directly into the objective's
  value / gradient / Hessian-vector product (weight ``alpha·λ`` ... for
  elastic net the split is ``l1 = α·λ``, ``l2 = (1−α)·λ``);
- the **L1 part** is non-smooth and is NOT part of the differentiable
  objective — it is handled by the optimizer (OWL-QN's orthant-wise
  projection), exactly as Breeze's OWLQN does for the reference.

The intercept column can be excluded from regularization via
``intercept_index`` (the reference excludes the intercept when
``addIntercept`` is on).
"""

from __future__ import annotations

import enum

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

Array = jax.Array


class RegularizationType(str, enum.Enum):
    NONE = "NONE"
    L1 = "L1"
    L2 = "L2"
    ELASTIC_NET = "ELASTIC_NET"


@jax.jit
def _half_l2_sq_norm(l2_weight: Array, wm: Array) -> Array:
    """½·λ₂·‖wm‖² as ONE jitted program.

    ``value_and_gradient`` adds the reg term outside the jitted chunk
    programs, and an eager ``0.5 * array`` uploads a fresh host scalar
    every evaluation — a per-pass implicit host→device transfer that
    ``analysis.guards.no_implicit_transfers`` rejects.  Inside a jit the
    0.5 is a literal of the compiled program: nothing is uploaded, on
    the eager path or under any outer trace (jit, vmap, shard_map)."""
    return 0.5 * l2_weight * jnp.vdot(wm, wm)


@struct.dataclass
class RegularizationContext:
    """Split of the regularization weight into smooth (l2) and l1 parts.

    ``reg_mask`` (optional, [dim]) zeroes regularization on chosen
    coordinates (used to exempt the intercept).
    """

    l1_weight: Array  # scalar
    l2_weight: Array  # scalar
    reg_mask: Array | None = None  # [dim] or None (regularize everything)

    # -- constructors -------------------------------------------------------

    @staticmethod
    def none() -> "RegularizationContext":
        return RegularizationContext(
            l1_weight=jnp.asarray(0.0), l2_weight=jnp.asarray(0.0)
        )

    @staticmethod
    def l2(weight: float, reg_mask: Array | None = None) -> "RegularizationContext":
        return RegularizationContext(
            l1_weight=jnp.asarray(0.0),
            l2_weight=jnp.asarray(weight, jnp.float32),
            reg_mask=reg_mask,
        )

    @staticmethod
    def l1(weight: float, reg_mask: Array | None = None) -> "RegularizationContext":
        return RegularizationContext(
            l1_weight=jnp.asarray(weight, jnp.float32),
            l2_weight=jnp.asarray(0.0),
            reg_mask=reg_mask,
        )

    @staticmethod
    def elastic_net(
        weight: float, alpha: float, reg_mask: Array | None = None
    ) -> "RegularizationContext":
        """Reference convention: l1 = α·λ, l2 = (1−α)·λ."""
        return RegularizationContext(
            l1_weight=jnp.asarray(alpha * weight, jnp.float32),
            l2_weight=jnp.asarray((1.0 - alpha) * weight, jnp.float32),
            reg_mask=reg_mask,
        )

    # -- smooth (L2) part ---------------------------------------------------

    def _masked(self, w: Array) -> Array:
        return w if self.reg_mask is None else w * self.reg_mask

    def l2_value(self, w: Array) -> Array:
        wm = self._masked(w)
        return _half_l2_sq_norm(self.l2_weight, wm)

    def l2_gradient(self, w: Array) -> Array:
        return self.l2_weight * self._masked(w)

    def l2_hessian_vector(self, v: Array) -> Array:
        return self.l2_weight * self._masked(v)

    def l2_hessian_diagonal(self, w: Array) -> Array:
        ones = jnp.ones_like(w)
        return self.l2_weight * self._masked(ones)

    # -- non-smooth (L1) part — optimizer-facing ----------------------------

    def l1_value(self, w: Array) -> Array:
        return self.l1_weight * jnp.sum(jnp.abs(self._masked(w)))


def exclude_intercept_mask(dim: int, intercept_index: int | None) -> Array | None:
    """[dim] mask that exempts the intercept coordinate, or None."""
    if intercept_index is None:
        return None
    return jnp.ones((dim,), jnp.float32).at[intercept_index].set(0.0)


@struct.dataclass
class SweptRegularization:
    """Per-lane regularization weights for a batched λ sweep.

    One lane per λ-grid point: ``l1_weights[l]`` / ``l2_weights[l]`` are
    the lane's split under the same reference convention as
    ``RegularizationContext`` (L2 → (0, λ); L1 → (λ, 0); elastic net →
    (α·λ, (1−α)·λ)).  The shared ``reg_mask`` (intercept exemption)
    stays on the base context — lanes differ only in weight.
    """

    l1_weights: Array  # [L]
    l2_weights: Array  # [L]

    @staticmethod
    def from_grid(
        regularization: "RegularizationType | str",
        weights,
        elastic_net_alpha: float = 0.5,
    ) -> "SweptRegularization":
        """λ grid [L] → per-lane (l1, l2) splits."""
        lam = jnp.asarray(np.asarray(weights, np.float32))
        reg = RegularizationType(regularization)
        if reg == RegularizationType.L2:
            l1, l2 = jnp.zeros_like(lam), lam
        elif reg == RegularizationType.L1:
            l1, l2 = lam, jnp.zeros_like(lam)
        elif reg == RegularizationType.ELASTIC_NET:
            l1 = elastic_net_alpha * lam
            l2 = (1.0 - elastic_net_alpha) * lam
        else:  # NONE
            l1, l2 = jnp.zeros_like(lam), jnp.zeros_like(lam)
        return SweptRegularization(l1_weights=l1, l2_weights=l2)

    @property
    def n_lanes(self) -> int:
        return self.l1_weights.shape[0]

    def has_l1(self) -> bool:
        """Concrete any-lane L1 presence (OWL-QN routing for the whole
        sweep; must be decided outside jit, like ``OptimizationProblem
        .has_l1``).  A zero-λ lane inside an L1 sweep rides the OWL-QN
        loop with an all-zero l1 vector."""
        return bool(np.any(np.asarray(self.l1_weights) != 0.0))

    def l1_vectors(self, dim: int, reg_mask: Array | None) -> Array:
        """Per-lane [L, dim] OWL-QN weight vectors (mask applied)."""
        vecs = jnp.broadcast_to(
            self.l1_weights[:, None].astype(jnp.float32),
            (self.n_lanes, dim),
        )
        if reg_mask is not None:
            vecs = vecs * reg_mask
        return vecs
