"""Optimization problems: bind (objective, optimizer, regularization).

Reference counterparts: ``GeneralizedLinearOptimizationProblem`` /
``SingleNodeOptimizationProblem`` / ``DistributedOptimizationProblem``
(photon-api ``com.linkedin.photon.ml.optimization`` [expected paths, mount
unavailable — see SURVEY.md]).

A problem is the solvable unit GAME coordinates hold: it knows which
solver to run (L-BFGS / OWL-QN by L1-presence / TRON), with what config,
against which ``GLMObjective``.  ``run`` is a pure function of
``(batch, w0)`` so:

- the single-node form IS the reference's ``SingleNodeOptimizationProblem``
  (used per-entity under vmap — see ``solve_batched``), and
- the distributed form is the SAME problem whose batch is sharded and whose
  objective psums internally (``photon_ml_tpu.parallel``): unlike the
  reference, no separate Distributed/SingleNode class pair is needed —
  distribution is a property of the data sharding, not the algorithm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.data.batch import Batch
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.base import (
    MarginSplit,
    OptimizationResult,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.lbfgs import lbfgs_solve
from photon_ml_tpu.optim.tron import tron_solve

Array = jax.Array


def as_margin_split(obj: GLMObjective, batch: Batch) -> MarginSplit:
    """The objective over ``batch``, split at its margins for
    ``lbfgs_solve``."""
    return MarginSplit(
        margins=lambda w: obj.margins(w, batch),
        margin_step=lambda d: obj.margin_step(d, batch),
        value=lambda m, w: obj.value_from_margins(m, w, batch),
        value_and_grad=lambda m, w: obj.value_and_gradient_from_margins(
            m, w, batch),
    )


@struct.dataclass
class OptimizationProblem:
    """(objective, optimizer type, config) — the solvable unit.

    ``optimizer`` and ``config`` are static; the objective is a pytree
    (its reg/norm arrays trace).  L1 weight lives on the objective's
    ``RegularizationContext`` and routes L-BFGS → OWL-QN automatically,
    mirroring the reference's optimizer selection.
    """

    objective: GLMObjective
    optimizer: OptimizerType = struct.field(
        pytree_node=False, default=OptimizerType.LBFGS
    )
    config: OptimizerConfig = struct.field(
        pytree_node=False, default_factory=OptimizerConfig
    )

    def has_l1(self) -> bool:
        """Concrete L1-presence — decides L-BFGS vs OWL-QN routing.
        Must be evaluated OUTSIDE jit (at problem construction the reg
        weight is a concrete scalar; under trace it is a tracer and the
        routing, being control flow, cannot depend on it)."""
        try:
            return float(self.objective.reg.l1_weight) != 0.0
        except (TypeError, jax.errors.TracerArrayConversionError) as e:
            raise ValueError(
                "has_l1 must be decided on a concrete objective; pass "
                "has_l1= explicitly when calling run() under jit"
            ) from e

    def _l1_vector(self, dim: int) -> Array:
        reg = self.objective.reg
        vec = jnp.broadcast_to(
            jnp.asarray(reg.l1_weight, jnp.float32), (dim,)
        )
        if reg.reg_mask is not None:
            vec = vec * reg.reg_mask
        return vec

    def run(self, batch: Batch, w0: Array,
            has_l1: bool | None = None) -> OptimizationResult:
        """Solve for one batch from one starting point (jittable; when
        called under jit, ``has_l1`` must be supplied — see has_l1)."""
        obj = self.objective
        if has_l1 is None:
            has_l1 = self.has_l1()
        if self.optimizer == OptimizerType.TRON:
            if has_l1:
                raise ValueError(
                    "TRON requires a smooth objective; use LBFGS (OWL-QN) "
                    "for L1/elastic-net problems"
                )
            vg = lambda w: obj.value_and_gradient(w, batch)
            hvp = lambda w, v: obj.hessian_vector(w, v, batch)
            return tron_solve(vg, hvp, w0, self.config)
        l1 = self._l1_vector(w0.shape[-1]) if has_l1 else None
        return lbfgs_solve(as_margin_split(obj, batch), w0, self.config,
                           l1_weight=l1)


def solve_batched(
    problem: OptimizationProblem, batches: Batch, w0s: Array
) -> OptimizationResult:
    """vmap ``problem.run`` over stacked problems (leading axis).

    This is the TPU replacement for the reference's per-entity
    ``SingleNodeOptimizationProblem`` loops inside
    ``RandomEffectCoordinate``: ``batches`` holds B same-shape entity
    blocks ([B, n, ...]), ``w0s`` is [B, dim]; each lane converges on its
    own criterion (masked while_loop).  Returns a batched
    ``OptimizationResult`` with leading dim B.
    """
    return jax.vmap(problem.run)(batches, w0s)
