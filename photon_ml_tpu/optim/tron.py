"""TRON: trust-region Newton with a conjugate-gradient inner loop.

Reference counterpart: ``TRON.scala`` (photon-lib
``com.linkedin.photon.ml.optimization``, itself a port of LIBLINEAR's TRON,
Lin & Moré 1999 [expected path, mount unavailable — see SURVEY.md]).

Structure matches the reference algorithm:

- outer loop: Steihaug-CG-solve ``H p = −g`` inside trust radius Δ, take
  the step if the actual/predicted reduction ratio ρ clears η₀, update Δ
  by the standard σ thresholds;
- inner CG: Hessian-vector products only (never a materialized Hessian) —
  on TPU each HVP is the same fused batch pipeline as a gradient, so a CG
  step costs about one extra data pass, exactly the property that made
  TRON attractive on Spark (one treeAggregate per HVP).

Both loops are ``lax.while_loop``s with converged-lane guards, so the
solver is jittable and vmappable (per-entity TRON for random effects).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from photon_ml_tpu.optim.base import (
    Hvp,
    OptimizationResult,
    OptimizerConfig,
    StatesTracker,
    ValueAndGrad,
    grad_converged,
    loss_converged,
)

Array = jax.Array

# LIBLINEAR/Lin-Moré trust-region constants.
_ETA0 = 1e-4   # minimum ρ to accept a step
_SIGMA1 = 0.25  # shrink factor on poor steps
_SIGMA2 = 0.5
_SIGMA3 = 4.0   # growth factor on very good boundary steps
_DELTA_MIN = 1e-12


def _boundary_tau(p: Array, d: Array, delta: Array) -> Array:
    """τ ≥ 0 with ‖p + τ·d‖ = Δ (largest root of the quadratic).

    Numerically hardened for f32 (ISSUE 17): when p already sits on the
    boundary to rounding (‖p‖² ⩾ Δ² by an ulp, which CG's accumulated
    float32 updates produce), ``Δ² − pp`` goes negative-by-epsilon and
    the classic ``(disc − pd)/dd`` numerator cancels catastrophically
    for pd > 0 — the clamped discriminant then yields a small NEGATIVE
    τ, a backward step that exits CG inside the region while reporting
    a boundary hit (and an unguarded discriminant would be NaN, which
    poisons the whole CG carry).  Pick the cancellation-free root form
    per sign(pd) and clamp τ at 0.
    """
    dd = jnp.maximum(jnp.vdot(d, d), 1e-30)
    pd = jnp.vdot(p, d)
    pp = jnp.vdot(p, p)
    gap = delta * delta - pp
    disc = jnp.sqrt(jnp.maximum(pd * pd + dd * gap, 0.0))
    # Largest root of dd·τ² + 2·pd·τ − gap = 0.  The (disc − pd) form
    # subtracts near-equal magnitudes when pd > 0; its conjugate
    # gap/(pd + disc) is exact there and degrades gracefully (τ → 0)
    # when gap underflows negative.
    tau = jnp.where(pd > 0.0,
                    gap / jnp.maximum(pd + disc, 1e-30),
                    (disc - pd) / dd)
    return jnp.maximum(tau, 0.0)


def _steihaug_cg(
    hvp_w, g: Array, delta: Array, config: OptimizerConfig
) -> tuple[Array, Array, Array]:
    """Approximately solve H p = −g within ‖p‖ ≤ Δ.

    Returns (p, hit_boundary, cg_iters).  Stops on the forcing condition
    ‖r‖ ≤ cg_tolerance·‖g‖, the iteration cap, or the trust boundary
    (negative curvature cannot occur for convex GLM objectives but is
    handled identically to the boundary case for safety).
    """
    g_norm = jnp.linalg.norm(g)
    tol = config.cg_tolerance * g_norm

    def cond(state):
        p, r, d, rs, it, done, boundary = state
        return jnp.logical_and(jnp.logical_not(done), it < config.cg_max_iters)

    def body(state):
        p, r, d, rs, it, done, boundary = state
        hd = hvp_w(d)
        dhd = jnp.vdot(d, hd)
        # Negative/zero curvature → march to the boundary along d.
        neg_curv = dhd <= 0.0
        alpha = jnp.where(neg_curv, 0.0, rs / jnp.maximum(dhd, 1e-30))
        p_try = p + alpha * d
        outside = jnp.linalg.norm(p_try) >= delta
        take_boundary = jnp.logical_or(neg_curv, outside)
        tau = _boundary_tau(p, d, delta)
        p_new = jnp.where(take_boundary, p + tau * d, p_try)
        r_new = r - alpha * hd
        rs_new = jnp.vdot(r_new, r_new)
        finished = jnp.logical_or(take_boundary, jnp.sqrt(rs_new) <= tol)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        d_new = r_new + beta * d
        keep = lambda new, old: jnp.where(done, old, new)
        return (
            keep(p_new, p), keep(r_new, r), keep(d_new, d), keep(rs_new, rs),
            keep(it + 1, it),
            jnp.logical_or(done, finished),
            jnp.logical_or(boundary, jnp.logical_and(jnp.logical_not(done),
                                                     take_boundary)),
        )

    p0 = jnp.zeros_like(g)
    r0 = -g
    init = (
        p0, r0, r0, jnp.vdot(r0, r0), jnp.asarray(0, jnp.int32),
        g_norm <= 0.0, jnp.asarray(False),
    )
    p, *_rest = jax.lax.while_loop(cond, body, init)
    boundary = _rest[-1]
    cg_iters = _rest[3]
    return p, boundary, cg_iters


@struct.dataclass
class _TronCarry:
    w: Array
    f: Array
    g: Array
    delta: Array
    iteration: Array
    done: Array
    converged: Array
    g0_norm: Array
    tracker: StatesTracker
    # what the solve paid, counted whether or not states are tracked
    cg_steps: Array
    hvp_passes: Array
    forward_passes: Array


def tron_solve(
    value_and_grad: ValueAndGrad,
    hvp: Hvp,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> OptimizationResult:
    """Minimize a twice-differentiable objective by trust-region Newton.

    ``hvp(w, v)`` must return ``H(w)·v`` including the L2 term (the
    objective's ``hessian_vector`` does).  L1 is not supported — the
    reference likewise restricts TRON to smooth objectives.
    """
    f0, g0 = value_and_grad(w0)
    g0_norm = jnp.linalg.norm(g0)

    tracker = StatesTracker.create(config.max_iters)
    if config.track_states:
        tracker = tracker.record(jnp.asarray(0, jnp.int32), f0, g0_norm)

    already = grad_converged(g0_norm, g0_norm, config.tolerance)
    init = _TronCarry(
        w=w0, f=f0, g=g0,
        delta=g0_norm,  # LIBLINEAR's initial radius
        iteration=jnp.asarray(0, jnp.int32),
        done=already, converged=already,
        g0_norm=g0_norm, tracker=tracker,
        cg_steps=jnp.asarray(0, jnp.int32),
        hvp_passes=jnp.asarray(0, jnp.int32),
        forward_passes=jnp.asarray(1, jnp.int32),
    )

    def cond(c: _TronCarry):
        return jnp.logical_and(
            jnp.logical_not(c.done), c.iteration < config.max_iters
        )

    def body(c: _TronCarry):
        hvp_w = lambda v: hvp(c.w, v)
        p, _, cg_iters = _steihaug_cg(hvp_w, c.g, c.delta, config)

        f_new, g_new = value_and_grad(c.w + p)
        actual = c.f - f_new
        predicted = -(jnp.vdot(c.g, p) + 0.5 * jnp.vdot(p, hvp_w(p)))
        rho = actual / jnp.maximum(predicted, 1e-30)

        accept = jnp.logical_and(rho > _ETA0, actual > 0.0)
        p_norm = jnp.linalg.norm(p)
        # Radius update (Lin & Moré simplified schedule, as in LIBLINEAR):
        delta = jnp.where(
            rho < _SIGMA1,
            jnp.minimum(c.delta, p_norm) * _SIGMA1,
            jnp.where(rho > 0.75, jnp.maximum(c.delta, _SIGMA3 * p_norm / 2.0),
                      c.delta),
        )
        delta = jnp.maximum(delta, _DELTA_MIN)

        w = jnp.where(accept, c.w + p, c.w)
        f = jnp.where(accept, f_new, c.f)
        g = jnp.where(accept, g_new, c.g)
        g_norm = jnp.linalg.norm(g)

        conv = jnp.logical_or(
            grad_converged(g_norm, c.g0_norm, config.tolerance),
            jnp.logical_and(accept,
                            loss_converged(f_new, c.f, config.rel_tolerance)),
        )
        # Numerical-precision stop: when the model predicts less reduction
        # than float32 can measure on |f|, further iterations only reject
        # steps and shrink Δ — stop and report converged (no measurable
        # progress is possible at this precision).
        precision_floor = 1e-6 * jnp.maximum(jnp.abs(c.f), 1.0)
        numerical_stop = predicted <= precision_floor
        conv = jnp.logical_or(conv, numerical_stop)
        stalled = delta <= _DELTA_MIN
        it = c.iteration + 1
        tracker = (
            c.tracker.record(it, f, g_norm,
                             step_size=jnp.where(accept, p_norm, 0.0),
                             ls_trials=cg_iters)
            if config.track_states else c.tracker
        )

        hvps = cg_iters + 1   # one product a CG step, one for `predicted`
        # Forward contractions, counted where they are made: the
        # evaluation at w + p, and X·w and X·v in each product.  (XLA
        # drops a product's X·w where the loss's d2 reads no margin, as
        # the squared loss's does.)
        keep = lambda new, old: jnp.where(c.done, old, new)
        return _TronCarry(
            w=keep(w, c.w), f=keep(f, c.f), g=keep(g, c.g),
            delta=keep(delta, c.delta),
            iteration=keep(it, c.iteration),
            done=jnp.logical_or(c.done, jnp.logical_or(conv, stalled)),
            converged=jnp.logical_or(c.converged, conv),
            g0_norm=c.g0_norm,
            tracker=jax.tree.map(keep, tracker, c.tracker),
            cg_steps=keep(c.cg_steps + cg_iters, c.cg_steps),
            hvp_passes=keep(c.hvp_passes + hvps, c.hvp_passes),
            forward_passes=keep(c.forward_passes + 1 + 2 * hvps,
                                c.forward_passes),
        )

    final = jax.lax.while_loop(cond, body, init)
    return OptimizationResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(final.g),
        iterations=final.iteration,
        converged=final.converged,
        tracker=final.tracker,
        forward_passes=final.forward_passes,
        cg_steps=final.cg_steps,
        hvp_passes=final.hvp_passes,
    )
