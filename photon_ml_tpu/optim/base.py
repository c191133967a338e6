"""Optimizer substrate: configs, results, convergence, state tracking.

Reference counterparts: ``Optimizer`` / ``OptimizerConfig`` /
``OptimizerState`` / ``OptimizationStatesTracker``
(photon-lib ``com.linkedin.photon.ml.optimization`` [expected paths, mount
unavailable — see SURVEY.md]).

The reference's ``Optimizer`` is a JVM iteration loop with mutable history;
here every solver is a **pure function** ``(objective fns, w0, config) →
OptimizationResult`` whose loop is a ``lax.while_loop``.  That makes one
solver serve all three execution contexts the framework needs:

- **jit** for the fixed-effect solve (one big problem),
- **vmap** for random-effect solves (thousands of small problems at once —
  the reference's per-entity Scala loops become one batched program), and
- **shard_map** transparently, because the objective callables close over
  sharded batches and psum internally.

vmap semantics: ``lax.while_loop`` under vmap iterates until *every* lane's
predicate is false, so each solver carries a ``converged`` flag and guards
its update with ``jnp.where`` — converged lanes coast unchanged while
stragglers finish (SURVEY.md §7 "masked while_loop semantics").

Convergence mirrors the reference's two criteria: relative gradient-norm
tolerance (``‖g‖ ≤ tol·max(1,‖g₀‖)``) and relative loss-change tolerance.
``OptimizationStatesTracker`` history is kept as fixed-shape [max_iters+1]
arrays written with ``.at[i].set`` — static shapes, jit/vmap friendly.
"""

from __future__ import annotations

import enum
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from flax import struct

Array = jax.Array

# Objective callables: value_and_grad(w) -> (f, g);  hvp(w, v) -> Hv.
ValueAndGrad = Callable[[Array], tuple[Array, Array]]
Hvp = Callable[[Array, Array], Array]


class MarginSplit(NamedTuple):
    """An objective over one batch whose margins are affine in w (a GLM:
    ``ops.objective.GLMObjective``), split at the margins: what
    ``lbfgs_solve`` needs to search along ``m + a·X·d`` instead of
    evaluating ``w + a·d`` from scratch, or, where OWL-QN's projection
    bends the step, to take a gradient from the margins a trial already
    paid for.  ``optim.problem`` builds it."""

    margins: Callable[[Array], Array]             # w → m = X·w + o
    margin_step: Callable[[Array], Array]         # d → X·d
    value: Callable[[Array, Array], Array]        # (m, w) → f(w), m = margins(w)
    value_and_grad: Callable[[Array, Array], tuple[Array, Array]]  # the same → (f, ∇f)


class OptimizerType(str, enum.Enum):
    """Reference ``OptimizerType`` enum (LBFGS / TRON; OWL-QN is selected
    automatically when L1 regularization is present, as in the reference)."""

    LBFGS = "LBFGS"
    TRON = "TRON"


@struct.dataclass
class OptimizerConfig:
    """Solver hyperparameters (reference ``OptimizerConfig``).

    All fields are static Python numbers so a config change retriggers
    compilation (shapes depend on ``max_iters`` / ``lbfgs_memory``).
    """

    max_iters: int = struct.field(pytree_node=False, default=100)
    # ‖g‖₂ ≤ tolerance · max(1, ‖g₀‖₂)  (Breeze/reference-style relative
    # gradient convergence).
    tolerance: float = struct.field(pytree_node=False, default=1e-7)
    # |f_k − f_{k−1}| ≤ rel_tolerance · max(1, |f_k|).
    rel_tolerance: float = struct.field(pytree_node=False, default=0.0)
    # L-BFGS two-loop memory (Breeze default m=10).
    lbfgs_memory: int = struct.field(pytree_node=False, default=10)
    # Backtracking line search: shrink factor / Armijo c1 / max halvings.
    ls_shrink: float = struct.field(pytree_node=False, default=0.5)
    ls_c1: float = struct.field(pytree_node=False, default=1e-4)
    ls_max_steps: int = struct.field(pytree_node=False, default=30)
    # TRON inner CG: max iterations and forcing tolerance ‖r‖ ≤ cg_tol·‖g‖.
    cg_max_iters: int = struct.field(pytree_node=False, default=50)
    cg_tolerance: float = struct.field(pytree_node=False, default=0.1)
    # Record per-iteration (value, grad_norm) history.
    track_states: bool = struct.field(pytree_node=False, default=True)


@struct.dataclass
class StatesTracker:
    """Fixed-shape per-iteration history (reference
    ``OptimizationStatesTracker``): ``values[i]`` / ``grad_norms[i]`` hold
    the state after iteration i (slot 0 = initial point); ``count`` is the
    number of valid slots.  Unwritten slots are NaN.

    ``step_sizes[i]`` / ``ls_trials[i]`` (ISSUE 8 convergence traces)
    record the accepted line-search step and the number of objective
    trials iteration i paid (TRON records the step NORM and the inner-CG
    iteration count instead — the analogous per-iteration cost).  Both
    planes are optional pytree leaves: a ``None`` stays ``None`` through
    every ``record``/``tree.map`` so pre-existing direct constructions
    (the swept streaming solver assembles trackers by hand) keep their
    treedef."""

    values: Array      # [max_iters + 1]
    grad_norms: Array  # [max_iters + 1]
    count: Array       # int32 scalar
    step_sizes: Array | None = None  # [max_iters + 1] accepted α (TRON: ‖p‖)
    ls_trials: Array | None = None   # [max_iters + 1] trials (TRON: CG iters)

    @staticmethod
    def create(max_iters: int) -> "StatesTracker":
        nan = jnp.full((max_iters + 1,), jnp.nan, jnp.float32)
        return StatesTracker(values=nan, grad_norms=nan,
                             count=jnp.asarray(0, jnp.int32),
                             step_sizes=nan, ls_trials=nan)

    def record(self, i: Array, value: Array, grad_norm: Array,
               step_size: Array | None = None,
               ls_trials: Array | None = None) -> "StatesTracker":
        def _set(plane, x):
            if plane is None:
                return None
            if x is None:
                return plane
            return plane.at[i].set(
                jnp.asarray(x, jnp.float32).astype(jnp.float32))
        return StatesTracker(
            values=self.values.at[i].set(value.astype(jnp.float32)),
            grad_norms=self.grad_norms.at[i].set(grad_norm.astype(jnp.float32)),
            count=jnp.maximum(self.count, i.astype(jnp.int32) + 1),
            step_sizes=_set(self.step_sizes, step_size),
            ls_trials=_set(self.ls_trials, ls_trials),
        )


@struct.dataclass
class OptimizationResult:
    """What a solve returns — the reference's final ``OptimizerState`` plus
    its tracker, as one pytree (vmap gives these a leading batch dim)."""

    w: Array            # [dim] solution
    value: Array        # scalar final objective value
    grad_norm: Array    # scalar final ‖g‖₂
    iterations: Array   # int32 iterations executed
    converged: Array    # bool: tolerance met (vs iteration-capped)
    tracker: StatesTracker
    # int32 forward contractions X·v the solve made, counted in its
    # carry: L-BFGS along the margins makes iterations + 1; OWL-QN
    # through a ``MarginSplit`` 1 + ls_trials − walked_trials + the
    # searches that contracted an X·d (a trial the orthant projection
    # clips contracts its own point, one it clips nothing is scored along
    # m + a·X·d, and the accepted point's gradient is taken from the
    # margins its last trial kept), and batched by vmap, where it walks
    # nothing, 1 + ls_trials; a solve that evaluates each trial from w (a
    # swept lane, a bare callable) 1 + ls_trials + iterations, the
    # accepted point of every search being evaluated once more.  None
    # from a solver that counts none (the streamed solvers).  TRON's: 1 for
    # its start, 1 for each outer iteration's evaluation at w + p, and 2
    # for each Hessian-vector product (X·w for its curvature, and X·v).
    forward_passes: Array | None = None
    # int32 line-search trials, where a trial may be a forward
    # contraction (then a cost of its own); None along the margins, where
    # a trial is [rows]-vector work and the tracker's plane has them when
    # tracked.
    ls_trials: Array | None = None
    # int32, TRON's alone: the inner conjugate-gradient steps over all
    # outer iterations, and the Hessian-vector products they and the
    # ratio's one an outer iteration made (cg_steps + iterations).
    cg_steps: Array | None = None
    hvp_passes: Array | None = None
    # int32, OWL-QN's through a ``MarginSplit`` and unbatched alone: the
    # trials of ls_trials the orthant projection clipped nothing of,
    # scored along m + a·X·d without a contraction of their own.
    walked_trials: Array | None = None


def grad_converged(g_norm: Array, g0_norm: Array, tolerance: float) -> Array:
    return g_norm <= tolerance * jnp.maximum(1.0, g0_norm)


def loss_converged(f_new: Array, f_old: Array, rel_tolerance: float) -> Array:
    if rel_tolerance <= 0.0:
        return jnp.asarray(False)
    return jnp.abs(f_new - f_old) <= rel_tolerance * jnp.maximum(
        jnp.abs(f_new), 1.0
    )
