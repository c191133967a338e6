"""L-BFGS and OWL-QN as pure-JAX ``lax.while_loop`` solvers.

Reference counterparts: ``LBFGS.scala`` / ``OWLQN.scala`` (photon-lib
``com.linkedin.photon.ml.optimization``, thin wrappers over Breeze's
``LBFGS``/``OWLQN`` [expected paths, mount unavailable — see SURVEY.md]).

TPU-native design notes:

- The two-loop recursion runs over a **fixed-size circular buffer** of
  (s, y) pairs ([m, dim] arrays) with masking for unfilled slots — static
  shapes, so one compilation serves every iteration, and ``vmap`` batches
  the buffers over problems.
- Line search is backtracking Armijo (sufficient decrease) with a curvature
  skip-guard on the (s, y) update (``sᵀy > ε‖s‖‖y‖``) in place of Breeze's
  strong-Wolfe search: same convergence class on convex GLM objectives,
  far simpler under jit/vmap (no data-dependent bracketing structure).
- **OWL-QN is the same loop** with three hooks switched on when an L1
  weight is present, exactly the Breeze specialization structure:
  (1) the *pseudo-gradient* replaces the gradient in direction finding and
  convergence, (2) the search direction is projected onto the
  pseudo-gradient's descent orthant, (3) line-search iterates are projected
  onto the starting orthant and scored with the L1-inclusive objective.
  Curvature pairs use smooth gradients, as in Breeze.
- Every update is guarded by ``done`` so converged vmap lanes coast (see
  optim.base docstring).
- **A GLM's margins are affine in w**, ``X·(w + α·d) + o = (X·w + o) +
  α·(X·d)``.  Handed the objective split at its margins
  (``optim.base.MarginSplit``) and no L1 term, the solve carries
  ``m = X·w + o``, contracts ``X·d`` once an iteration, and every
  line-search trial is [rows]-vector work on ``m + α·X·d``: a solve makes
  ``iterations + 1`` forward contractions and as many transposed ones,
  where evaluating each trial from w pays one more forward contraction a
  trial.  OWL-QN's orthant projection bends ``w⁺ = w + α·d`` only where
  it clips a coordinate: a trial it clips contracts ``X·w⁺`` anew, one
  it clips nothing is scored along ``m + α·X·d`` like L-BFGS's, ``X·d``
  contracted by the first such trial of a search and kept for the rest
  (a search from w = 0 clips nothing).  The search keeps the margins of
  the trial it ends on, and the accepted point's gradient is taken from
  them: ``1 + ls_trials − walked_trials + xd_searches`` forward
  contractions, the last the searches that contracted an ``X·d``.  Under
  ``vmap`` a ``lax.cond`` whose predicate differs by lane runs both
  branches, so a batched solve (the random effects' per-entity lanes)
  does not walk: each of its trials contracts its own point, ``1 +
  ls_trials``, the program it had before the walk.  A bare
  ``value_and_grad`` callable shows no margins, so each trial is a whole
  evaluation and so is the accepted point's gradient: ``1 + ls_trials +
  iterations``.  The counts ride in the carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
# the type of a value traced for vmap, which JAX names nowhere public
from jax._src.interpreters.batching import BatchTracer

from photon_ml_tpu.optim.base import (
    MarginSplit,
    OptimizationResult,
    OptimizerConfig,
    StatesTracker,
    ValueAndGrad,
    grad_converged,
    loss_converged,
)

Array = jax.Array

_CURVATURE_EPS = 1e-10


@struct.dataclass
class _LbfgsCarry:
    w: Array          # [d]
    f: Array          # scalar — L1-inclusive value for OWL-QN
    g: Array          # [d] smooth gradient
    s_buf: Array      # [m, d] position diffs, circular
    y_buf: Array      # [m, d] gradient diffs, circular
    rho_buf: Array    # [m] 1/(sᵀy)
    head: Array       # int32 — next insert slot
    count: Array      # int32 — valid pairs (≤ m)
    iteration: Array  # int32
    done: Array       # bool — this lane finished (converged or stalled)
    converged: Array  # bool — finished due to tolerance
    g0_norm: Array    # scalar — initial gradient norm (for rel. tolerance)
    tracker: StatesTracker
    # where the objective is split at its margins, else None:
    margins: Array | None = None         # [n] X·w + o
    # counted in every mode:
    forward_passes: Array | None = None  # int32 — contractions X·v so far
    # counted where OWL-QN walks the trials that clip nothing, else None:
    ls_trials: Array | None = None       # int32 — line-search trials so far
    walked_trials: Array | None = None   # int32 — of them along m + α·X·d


def _pseudo_gradient(g: Array, w: Array, l1: Array) -> Array:
    """OWL-QN pseudo-gradient of f(w) + ‖l1 ⊙ w‖₁ (Andrew & Gao 2007).

    For w_j ≠ 0 the L1 term is differentiable; at w_j = 0 pick the one-sided
    derivative that points downhill, or 0 inside the subdifferential.
    """
    g_plus = g + l1
    g_minus = g - l1
    return jnp.where(
        w > 0.0,
        g_plus,
        jnp.where(
            w < 0.0,
            g_minus,
            jnp.where(g_minus > 0.0, g_minus, jnp.where(g_plus < 0.0, g_plus, 0.0)),
        ),
    )


def _two_loop(g_dir: Array, carry: _LbfgsCarry, m: int) -> Array:
    """Two-loop recursion over the circular (s, y) buffer → descent dir.

    Slot ages: pair j (0 = newest) lives at index (head − 1 − j) mod m.
    Masked for j ≥ count; with count == 0 this degrades to steepest descent.
    """
    q = g_dir

    def bwd(j, val):
        q, alphas = val
        idx = (carry.head - 1 - j) % m
        valid = j < carry.count
        alpha = carry.rho_buf[idx] * jnp.vdot(carry.s_buf[idx], q)
        alpha = jnp.where(valid, alpha, 0.0)
        q = q - alpha * carry.y_buf[idx]
        return q, alphas.at[j].set(alpha)

    q, alphas = jax.lax.fori_loop(
        0, m, bwd, (q, jnp.zeros((m,), g_dir.dtype))
    )

    # Initial Hessian scaling γ = sᵀy / yᵀy of the newest pair.
    newest = (carry.head - 1) % m
    y_new = carry.y_buf[newest]
    gamma = jnp.where(
        carry.count > 0,
        1.0 / jnp.maximum(carry.rho_buf[newest] * jnp.vdot(y_new, y_new),
                          _CURVATURE_EPS),
        1.0,
    )
    r = gamma * q

    def fwd(j_rev, r):
        j = m - 1 - j_rev  # oldest → newest
        idx = (carry.head - 1 - j) % m
        valid = j < carry.count
        beta = carry.rho_buf[idx] * jnp.vdot(carry.y_buf[idx], r)
        upd = carry.s_buf[idx] * (alphas[j] - beta)
        return r + jnp.where(valid, upd, 0.0)

    r = jax.lax.fori_loop(0, m, fwd, r)
    return -r


def _orthant(w: Array, pg: Array) -> Array:
    """OWL-QN search orthant ξ: sign(w), or sign(−pg) where w = 0."""
    return jnp.where(w != 0.0, jnp.sign(w), jnp.sign(-pg))


def _line_search(
    value_fn, w: Array, f0: Array, pg: Array, d: Array,
    config: OptimizerConfig, xi: Array | None,
) -> tuple[Array, Array, Array, Array, Array, Array | None]:
    """Backtracking Armijo; returns (w_new, f_new, ok, alpha, trials,
    kept).

    Sufficient-decrease test (Andrew & Gao's modified condition, which
    reduces to standard Armijo when there is no orthant projection):

        f(x⁺) ≤ f(x) + c1 · pgᵀ(x⁺ − x),   x⁺ = π(x + α·d; ξ)

    For OWL-QN (``xi`` given) trial points are projected onto the starting
    orthant and the slope uses the *actual* displacement x⁺ − x (which may
    differ from α·d where coordinates were clipped to zero).
    ``value_fn(α, x⁺, kept) → (f, kept)`` scores a trial: given α, a
    caller that knows the objective along the step need not start from
    x⁺; ``kept`` is whatever it wants of the trial (a pytree, None for
    nothing), handed to the next trial (None to the first) and returned
    of the trial the search ended on.
    """

    def trial(alpha, kept):
        w_try = w + alpha * d
        if xi is not None:
            w_try = jnp.where(jnp.sign(w_try) == xi, w_try, 0.0)
        return (w_try, *value_fn(alpha, w_try, kept))

    def accepts(w_try, f_try):
        return f_try <= f0 + config.ls_c1 * jnp.vdot(pg, w_try - w)

    def cond(state):
        _, w_try, f_try, _, steps = state
        return jnp.logical_and(
            jnp.logical_not(accepts(w_try, f_try)),
            steps < config.ls_max_steps,
        )

    def body(state):
        alpha, _, _, kept, steps = state
        alpha = alpha * config.ls_shrink
        return (alpha, *trial(alpha, kept), steps + 1)

    alpha0 = jnp.asarray(1.0, w.dtype)
    alpha, w_new, f_new, kept, steps = jax.lax.while_loop(
        cond, body, (alpha0, *trial(alpha0, None), jnp.asarray(0, jnp.int32))
    )
    ok = f_new < f0  # any strict decrease counts; stall otherwise
    return w_new, f_new, ok, alpha, steps + 1, kept


def _by_whole_evaluations(value_and_grad: ValueAndGrad, l1_vec):
    """How a solve evaluates its points when all it has is ``w → (f, g)``:
    every trial, and the accepted point once more, from w.
    ``(start, open_search)``:

    - ``start(w0) → (margins, counts, f_smooth, g)``;
    - ``open_search(c, d) → (trial, accept)``: ``trial(α, w_try, kept) →
      (f, kept)`` scores a point of the search, handed what the trial
      before it kept, and says what of it to keep; ``accept(α, w_new,
      trials, kept) → (margins, counts, g)`` takes the gradient where the
      search ended, after ``trials`` trials, handed what its last trial
      kept.

    ``counts`` are the carry's ``(forward_passes, ls_trials,
    walked_trials)``, None where a mode does not count one.  This mode
    carries no margins, keeps nothing and counts the contractions alone.
    Each evaluation is one: the start, every trial, every accepted
    point, so its trials are ``forward_passes − 1 − iterations``."""

    def start(w0):
        return (None, (jnp.asarray(1, jnp.int32), None, None),
                *value_and_grad(w0))

    def open_search(c, d):
        def trial(alpha, w_try, kept):
            f, _ = value_and_grad(w_try)
            return (f if l1_vec is None
                    else f + jnp.sum(l1_vec * jnp.abs(w_try))), None

        def accept(alpha, w_new, trials, kept):
            return (None, (c.forward_passes + trials + 1, None, None),
                    value_and_grad(w_new)[1])

        return trial, accept

    return start, open_search


def _start_at_margins(split: MarginSplit):
    """``start`` of the two modes that carry the margins."""

    def start(w0):
        m0 = split.margins(w0)
        return (m0, (jnp.asarray(1, jnp.int32), None, None),
                *split.value_and_grad(m0, w0))

    return start


def _along_margins(split: MarginSplit):
    """The same two functions for an objective split at its margins and
    a straight step: one forward contraction at the start and one an
    iteration (each counted where it is made); a trial is [rows]-vector
    work, and the accepted point's margins are known when its gradient
    is taken."""

    def open_search(c, d):
        xd, passes = split.margin_step(d), c.forward_passes + 1

        def trial(alpha, w_try, kept):
            return split.value(c.margins + alpha * xd, w_try), None

        def accept(alpha, w_new, trials, kept):
            m_new = c.margins + alpha * xd
            return (m_new, (passes, None, None),
                    split.value_and_grad(m_new, w_new)[1])

        return trial, accept

    return _start_at_margins(split), open_search


def _keeping_margins(split: MarginSplit, l1_vec):
    """And for a split objective whose step the orthant projection may
    bend (OWL-QN).  A trial that the projection clips is contracted from
    its own point, ``X·w_try + o``; one that it clips nothing is the
    straight step, scored along ``m + α·X·d`` with ``X·d`` contracted by
    the first such trial of the search and handed on to the next.  The
    search keeps the margins of the trial it ends on, and the accepted
    point's gradient is taken from them: no contraction at accept.

    Each contraction is counted where it is made, and so are the trials
    and those walked (``ls_trials``, ``walked_trials``): ``forward_passes
    = 1 + ls_trials − walked_trials + the searches that contracted X·d``.

    A solve batched by ``vmap`` does not walk: there the clip test
    differs by lane, ``lax.cond`` becomes a select that runs both
    branches, and a walked trial would pay its contraction all the same.
    Each of its trials contracts its own point, ``forward_passes = 1 +
    ls_trials``, and it counts nothing else (the program it had before
    the walk).  What the start's margins show decides: a batch tracer's
    are batched.  A solve traced alone and batched afterwards (``vmap``
    of a jitted solve) would walk both branches; nothing does that."""
    start_at_margins = _start_at_margins(split)

    def start(w0):
        m0, (passes, _, _), f, g = start_at_margins(w0)
        if isinstance(m0, BatchTracer):
            return m0, (passes, None, None), f, g
        zero = jnp.asarray(0, jnp.int32)
        return m0, (passes, zero, zero), f, g

    def open_search(c, d):
        def scored(m_try, w_try):
            return (split.value(m_try, w_try)
                    + jnp.sum(l1_vec * jnp.abs(w_try)))

        if c.walked_trials is None:      # batched: every trial contracts
            def trial(alpha, w_try, kept):
                m_try = split.margins(w_try)
                return scored(m_try, w_try), m_try

            def accept(alpha, w_new, trials, m_new):
                return (m_new, (c.forward_passes + trials, None, None),
                        split.value_and_grad(m_new, w_new)[1])

            return trial, accept

        def trial(alpha, w_try, kept):
            # (margins, X·d, whether X·d is contracted, trials walked)
            xd, known, walked = (
                (jnp.zeros_like(c.margins), jnp.asarray(False),
                 jnp.asarray(0, jnp.int32)) if kept is None else kept[1:])

            def contract(xd, known):
                return split.margins(w_try), xd, known, walked

            def walk(xd, known):
                xd = jax.lax.cond(known, lambda: xd,
                                  lambda: split.margin_step(d))
                return (c.margins + alpha * xd, xd, jnp.asarray(True),
                        walked + 1)

            clipped = jnp.any(w_try != c.w + alpha * d)
            kept = jax.lax.cond(clipped, contract, walk, xd, known)
            return scored(kept[0], w_try), kept

        def accept(alpha, w_new, trials, kept):
            m_new, _, known, walked = kept
            return (m_new,
                    (c.forward_passes + trials - walked
                     + known.astype(jnp.int32),
                     c.ls_trials + trials, c.walked_trials + walked),
                    split.value_and_grad(m_new, w_new)[1])

        return trial, accept

    return start, open_search


def lbfgs_solve(
    objective: ValueAndGrad | MarginSplit,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weight: Array | None = None,
) -> OptimizationResult:
    """Minimize a smooth objective (plus optional L1 term → OWL-QN).

    Args:
      objective: smooth part — ``w → (f_smooth, ∇f_smooth)``, or a GLM
        objective split at its margins (``optim.base.MarginSplit``):
        without an L1 term the line search then walks the margins, with
        one it walks those of the trials the orthant projection clips
        nothing of, unless batched, and keeps its last trial's for the
        accepted point's gradient (module docstring).  The L1 term must
        NOT be folded in; pass it via ``l1_weight``.
      w0: [dim] initial point.
      l1_weight: None (plain L-BFGS) or per-coordinate L1 weights [dim]
        (scalars broadcast), activating OWL-QN semantics.

    Jittable; vmap over (w0, closed-over batch) solves many problems at
    once with per-lane convergence.
    """
    m = config.lbfgs_memory
    d = w0.shape[-1]
    owlqn = l1_weight is not None
    l1_vec = (jnp.broadcast_to(jnp.asarray(l1_weight, w0.dtype), (d,))
              if owlqn else None)
    # what the objective shows decides how its points are evaluated, and
    # with that how the trials are read off the count of contractions
    if not isinstance(objective, MarginSplit):
        start, open_search = _by_whole_evaluations(objective, l1_vec)
        trials_of = lambda c: c.forward_passes - 1 - c.iteration
    elif owlqn:
        start, open_search = _keeping_margins(objective, l1_vec)
        trials_of = lambda c: (c.forward_passes - 1 if c.ls_trials is None
                               else c.ls_trials)
    else:
        start, open_search = _along_margins(objective)
        trials_of = lambda c: None

    m0, (passes0, trials0, walked0), f0_s, g0 = start(w0)
    f0 = f0_s + jnp.sum(l1_vec * jnp.abs(w0)) if owlqn else f0_s
    pg0 = _pseudo_gradient(g0, w0, l1_vec) if owlqn else g0
    g0_norm = jnp.linalg.norm(pg0)

    tracker = StatesTracker.create(config.max_iters)
    if config.track_states:
        tracker = tracker.record(jnp.asarray(0, jnp.int32), f0, g0_norm)

    already = grad_converged(g0_norm, g0_norm, config.tolerance)
    init = _LbfgsCarry(
        w=w0, f=f0, g=g0,
        s_buf=jnp.zeros((m, d), w0.dtype),
        y_buf=jnp.zeros((m, d), w0.dtype),
        rho_buf=jnp.zeros((m,), w0.dtype),
        head=jnp.asarray(0, jnp.int32),
        count=jnp.asarray(0, jnp.int32),
        iteration=jnp.asarray(0, jnp.int32),
        done=already,
        converged=already,
        g0_norm=g0_norm,
        tracker=tracker,
        margins=m0,
        forward_passes=passes0,
        ls_trials=trials0,
        walked_trials=walked0,
    )

    def cond(c: _LbfgsCarry):
        return jnp.logical_and(
            jnp.logical_not(c.done), c.iteration < config.max_iters
        )

    def body(c: _LbfgsCarry):
        pg = _pseudo_gradient(c.g, c.w, l1_vec) if owlqn else c.g
        d_dir = _two_loop(pg, c, m)
        if owlqn:
            # Constrain to the pseudo-gradient's descent orthant.
            d_dir = jnp.where(d_dir * -pg > 0.0, d_dir, 0.0)
            xi = _orthant(c.w, pg)
        else:
            xi = None
        # Safeguard: if not a descent direction (numerical breakdown),
        # restart from steepest descent.
        bad = jnp.vdot(pg, d_dir) >= 0.0
        d_dir = jnp.where(bad, -pg, d_dir)

        trial, accept = open_search(c, d_dir)
        w_new, f_new, ls_ok, alpha, trials, kept = _line_search(
            trial, c.w, c.f, pg, d_dir, config, xi
        )
        m_new, (passes, ls_trials, walked), g_new = accept(
            alpha, w_new, trials, kept)

        s = w_new - c.w
        y = g_new - c.g
        sy = jnp.vdot(s, y)
        good_pair = jnp.logical_and(
            ls_ok, sy > _CURVATURE_EPS * jnp.linalg.norm(s) * jnp.linalg.norm(y)
        )
        s_buf = jnp.where(good_pair, c.s_buf.at[c.head].set(s), c.s_buf)
        y_buf = jnp.where(good_pair, c.y_buf.at[c.head].set(y), c.y_buf)
        rho_buf = jnp.where(
            good_pair,
            c.rho_buf.at[c.head].set(1.0 / jnp.maximum(sy, _CURVATURE_EPS)),
            c.rho_buf,
        )
        head = jnp.where(good_pair, (c.head + 1) % m, c.head)
        count = jnp.where(good_pair, jnp.minimum(c.count + 1, m), c.count)

        pg_new = _pseudo_gradient(g_new, w_new, l1_vec) if owlqn else g_new
        g_norm = jnp.linalg.norm(pg_new)
        conv = jnp.logical_or(
            grad_converged(g_norm, c.g0_norm, config.tolerance),
            loss_converged(f_new, c.f, config.rel_tolerance),
        )
        # A full backtracking failure on a guaranteed descent direction
        # (the steepest-descent safeguard above) means the decrease is
        # below float32 measurement precision — report converged, since no
        # measurable progress is possible (Breeze similarly terminates on
        # LineSearchFailed and returns the current state).
        stalled = jnp.logical_not(ls_ok)
        conv = jnp.logical_or(conv, stalled)
        it = c.iteration + 1

        tracker = (
            c.tracker.record(it, f_new, g_norm,
                             step_size=jnp.where(ls_ok, alpha, 0.0),
                             ls_trials=trials)
            if config.track_states
            else c.tracker
        )

        # Converged-lane guard: if already done (only reachable under vmap
        # races), keep old state; otherwise commit.
        def keep(new, old):
            return jnp.where(c.done, old, new)

        def moved(new, old):
            """A rejected search keeps the point it started from."""
            return keep(jnp.where(ls_ok, new, old), old)

        return _LbfgsCarry(
            w=moved(w_new, c.w),
            f=moved(f_new, c.f),
            g=moved(g_new, c.g),
            s_buf=keep(s_buf, c.s_buf),
            y_buf=keep(y_buf, c.y_buf),
            rho_buf=keep(rho_buf, c.rho_buf),
            head=keep(head, c.head),
            count=keep(count, c.count),
            iteration=keep(it, c.iteration),
            done=jnp.logical_or(c.done, jnp.logical_or(conv, stalled)),
            converged=jnp.logical_or(c.converged, conv),
            g0_norm=c.g0_norm,
            tracker=jax.tree.map(keep, tracker, c.tracker),
            # None where the mode carries neither (an empty pytree); a
            # rejected search made its contraction all the same
            margins=jax.tree.map(moved, m_new, c.margins),
            forward_passes=jax.tree.map(keep, passes, c.forward_passes),
            ls_trials=jax.tree.map(keep, ls_trials, c.ls_trials),
            walked_trials=jax.tree.map(keep, walked, c.walked_trials),
        )

    final = jax.lax.while_loop(cond, body, init)
    pg_f = _pseudo_gradient(final.g, final.w, l1_vec) if owlqn else final.g
    return OptimizationResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(pg_f),
        iterations=final.iteration,
        converged=final.converged,
        tracker=final.tracker,
        forward_passes=final.forward_passes,
        ls_trials=trials_of(final),
        walked_trials=final.walked_trials,
    )


def owlqn_solve(
    value_and_grad: ValueAndGrad,
    w0: Array,
    l1_weight: Array,
    config: OptimizerConfig = OptimizerConfig(),
) -> OptimizationResult:
    """OWL-QN = L-BFGS with orthant-wise L1 handling (reference ``OWLQN``)."""
    return lbfgs_solve(value_and_grad, w0, config, l1_weight=l1_weight)


def lbfgs_solve_swept(
    value_and_grad,
    w0s: Array,
    lane_ctx,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weights: Array | None = None,
    use_map: bool = False,
) -> OptimizationResult:
    """Batched masked-lane L-BFGS / OWL-QN over L concurrent problems.

    The λ-sweep entry: one solve drives every grid point at once, so
    each objective evaluation inside the ``while_loop`` serves all L
    coefficient lanes against the SAME closed-over batch — one data
    stream amortized across the grid.  This is the proven
    masked-``while_loop`` vmap pattern of the random-effects bucket
    path (``game.coordinates._re_train_impl``): converged lanes coast
    under their ``done`` guard while stragglers finish.

    Args:
      value_and_grad: per-lane smooth objective
        ``(w [dim], lane_ctx_l) → (f, g)``; per-lane parameters (the
        lane's L2 weight, typically) ride in ``lane_ctx``.
      w0s: [L, dim] stacked starting points.
      lane_ctx: pytree whose leaves have leading axis L; row l is
        passed to ``value_and_grad`` for lane l.
      l1_weights: None (plain L-BFGS) or per-lane L1 weights — [L]
        scalars or [L, dim] vectors — activating OWL-QN semantics on
        EVERY lane (a zero row degrades to an all-zero l1 vector).
      use_map: run the lane axis as a ``lax.map`` loop instead of
        ``vmap`` — for objectives with no batching rule (GRR Pallas
        kernel, shard_mapped distributed objectives).  Still one
        compiled program over the whole grid; the amortization is then
        HBM-residency rather than a shared read.
    """
    if l1_weights is not None:
        def lane(args):
            w0, ctx, l1 = args
            return lbfgs_solve(lambda w: value_and_grad(w, ctx), w0,
                               config, l1_weight=l1)
        xs = (w0s, lane_ctx, l1_weights)
    else:
        def lane(args):
            w0, ctx = args
            return lbfgs_solve(lambda w: value_and_grad(w, ctx), w0, config)
        xs = (w0s, lane_ctx)
    if use_map:
        return jax.lax.map(lane, xs)
    return jax.vmap(lane)(xs)


def owlqn_solve_swept(
    value_and_grad,
    w0s: Array,
    lane_ctx,
    l1_weights: Array,
    config: OptimizerConfig = OptimizerConfig(),
    use_map: bool = False,
) -> OptimizationResult:
    """Batched-lane OWL-QN (see ``lbfgs_solve_swept``)."""
    return lbfgs_solve_swept(value_and_grad, w0s, lane_ctx, config,
                             l1_weights=l1_weights, use_map=use_map)
