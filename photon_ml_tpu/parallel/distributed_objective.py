"""The distributed GLM objective: shard_map + psum over the device mesh.

Reference counterpart — THE north-star component (BASELINE.json):
``DistributedGLMLossFunction`` / ``DistributedObjectiveFunction``
(photon-api ``com.linkedin.photon.ml.function.glm`` [expected path, mount
unavailable — see SURVEY.md §2.2]).  The reference's pattern per L-BFGS
iteration is:

    broadcast(w) → per-partition aggregator fold → treeAggregate partials

Here the whole pattern is one ``shard_map``ped function: ``w`` arrives
replicated (broadcast ≡ no-op), each device runs the SAME fused
``GLMObjective`` pipeline on its resident batch shard, and partial
(value, gradient, HVP) sums meet in a ``lax.psum`` — an ICI allreduce on
real hardware, which is the latency-critical hop the reference pays
driver↔executor round-trips for.

Exactness: every data-side quantity the objective computes is a linear
reduction over examples (including normalization's model-space algebra,
which is linear in (X^T r, Σr)), so per-shard partials + psum equal the
single-device result to float-summation reordering.  Regularization is
example-independent and is added OUTSIDE the psum, once.

The optimizers consume this through the same ``(value_and_grad, hvp)``
callables as the local objective — distribution is invisible to them
(see ``optim.problem`` docstring).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from flax import struct
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.data.batch import Batch
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.parallel.mesh import DATA_AXIS, batch_spec

Array = jax.Array


@struct.dataclass
class DistributedGLMObjective:
    """GLMObjective over a batch sharded on the mesh's data axis.

    Same ``TwiceDiffFunction`` surface as ``GLMObjective`` —
    ``OptimizationProblem`` and the solvers cannot tell them apart.
    ``mesh`` is static; the inner objective's reg/norm arrays trace.
    """

    objective: GLMObjective
    mesh: Mesh = struct.field(pytree_node=False)

    @property
    def _data_obj(self) -> GLMObjective:
        """The inner objective stripped of regularization: reg must be
        added once, outside the psum, not per-shard."""
        return self.objective.replace(reg=RegularizationContext.none())

    # Each method shard_maps a closure running the LOCAL fused pipeline and
    # psumming the [dim]-or-scalar partials.  w is replicated (in_spec P()),
    # batch leaves are example-sharded (P('data')).

    def value(self, w: Array, batch: Batch) -> Array:
        def local(w, batch):
            return jax.lax.psum(self._data_obj.value(w, batch), DATA_AXIS)

        val = shard_map(
            local, mesh=self.mesh, in_specs=(P(), batch_spec()),
            out_specs=P(),
        )(w, batch)
        return val + self.objective.reg.l2_value(w)

    def value_and_gradient(self, w: Array, batch: Batch) -> tuple[Array, Array]:
        def local(w, batch):
            v, g = self._data_obj.value_and_gradient(w, batch)
            return jax.lax.psum((v, g), DATA_AXIS)

        v, g = shard_map(
            local, mesh=self.mesh, in_specs=(P(), batch_spec()),
            out_specs=(P(), P()),
        )(w, batch)
        reg = self.objective.reg
        return v + reg.l2_value(w), g + reg.l2_gradient(w)

    def gradient(self, w: Array, batch: Batch) -> Array:
        return self.value_and_gradient(w, batch)[1]

    def hessian_vector(self, w: Array, v: Array, batch: Batch) -> Array:
        def local(w, v, batch):
            return jax.lax.psum(
                self._data_obj.hessian_vector(w, v, batch), DATA_AXIS
            )

        hv = shard_map(
            local, mesh=self.mesh, in_specs=(P(), P(), batch_spec()),
            out_specs=P(),
        )(w, v, batch)
        return hv + self.objective.reg.l2_hessian_vector(v)

    def hessian_diagonal(self, w: Array, batch: Batch) -> Array:
        def local(w, batch):
            return jax.lax.psum(
                self._data_obj.hessian_diagonal(w, batch), DATA_AXIS
            )

        hd = shard_map(
            local, mesh=self.mesh, in_specs=(P(), batch_spec()),
            out_specs=P(),
        )(w, batch)
        return hd + self.objective.reg.l2_hessian_diagonal(w)

    # Scoring: no reduction — per-example outputs stay sharded in place.
    def predict_margins(self, w: Array, batch: Batch) -> Array:
        return shard_map(
            lambda w, b: self._data_obj.predict_margins(w, b),
            mesh=self.mesh, in_specs=(P(), batch_spec()),
            out_specs=batch_spec(),
        )(w, batch)

    def x_dot(self, v: Array, batch: Batch) -> Array:
        """Raw X·v per example (coordinate scoring).  Must run under
        shard_map: a per-shard layout (GRR plan / colmajor) indexes only
        its device's rows, so the contraction is shard-local."""
        return shard_map(
            lambda v, b: b.x_dot(v),
            mesh=self.mesh, in_specs=(P(), batch_spec()),
            out_specs=batch_spec(),
        )(v, batch)
