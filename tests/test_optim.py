"""Optimizer tests: convergence to sklearn/scipy/closed-form optima.

Mirrors the reference's optimizer unit tests (LBFGS/OWLQN/TRON on convex
toy problems with known minima, SURVEY.md §4 tier 1) plus the rebuild's
extra obligation: the same solver must converge per-problem under vmap
(the random-effect prerequisite, SURVEY.md §7 "masked while_loop").
"""

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize
from sklearn.linear_model import LogisticRegression

from photon_ml_tpu.data.batch import make_dense_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.prior import GaussianPrior
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim import (
    OptimizationProblem,
    OptimizerConfig,
    OptimizerType,
    lbfgs_solve,
    owlqn_solve,
    tron_solve,
)


def _logistic_problem(rng, n=200, d=8, l2=1.0):
    x = rng.normal(0, 1, (n, d))
    w_true = rng.normal(0, 1, d)
    p = 1 / (1 + np.exp(-(x @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float64)
    batch = make_dense_batch(x, y)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )
    return x, y, batch, obj


def _sklearn_logistic(x, y, l2):
    # sklearn minimizes C·Σℓ + ½‖w‖² ⇔ ours (Σℓ + ½λ‖w‖²) with C = 1/λ.
    clf = LogisticRegression(
        C=1.0 / l2, fit_intercept=False, tol=1e-10, max_iter=10000
    )
    clf.fit(x, y)
    return clf.coef_.ravel()


CFG = OptimizerConfig(max_iters=200, tolerance=1e-5)


def test_lbfgs_logistic_matches_sklearn(rng):
    x, y, batch, obj = _logistic_problem(rng)
    res = lbfgs_solve(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros(x.shape[1], jnp.float32),
        CFG,
    )
    assert bool(res.converged)
    np.testing.assert_allclose(res.w, _sklearn_logistic(x, y, 1.0),
                               rtol=2e-3, atol=2e-4)


def test_tron_logistic_matches_sklearn(rng):
    x, y, batch, obj = _logistic_problem(rng)
    res = tron_solve(
        lambda w: obj.value_and_gradient(w, batch),
        lambda w, v: obj.hessian_vector(w, v, batch),
        jnp.zeros(x.shape[1], jnp.float32),
        CFG,
    )
    assert bool(res.converged)
    np.testing.assert_allclose(res.w, _sklearn_logistic(x, y, 1.0),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("opt", [OptimizerType.LBFGS, OptimizerType.TRON])
def test_ridge_matches_closed_form(rng, opt):
    n, d, l2 = 300, 10, 2.5
    x = rng.normal(0, 1, (n, d))
    y = x @ rng.normal(0, 1, d) + rng.normal(0, 0.1, n)
    batch = make_dense_batch(x, y)
    obj = GLMObjective(
        loss=losses.SQUARED,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )
    problem = OptimizationProblem(objective=obj, optimizer=opt, config=CFG)
    res = jax.jit(problem.run)(batch, jnp.zeros(d, jnp.float32))
    w_ref = np.linalg.solve(x.T @ x + l2 * np.eye(d), x.T @ y)
    assert bool(res.converged)
    np.testing.assert_allclose(res.w, w_ref, rtol=1e-4, atol=1e-5)


def test_poisson_matches_scipy(rng):
    n, d, l2 = 250, 6, 0.5
    x = rng.normal(0, 0.5, (n, d))
    lam = np.exp(x @ rng.normal(0, 0.5, d))
    y = rng.poisson(lam).astype(np.float64)
    batch = make_dense_batch(x, y)
    obj = GLMObjective(
        loss=losses.POISSON,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )

    def np_obj(w):
        z = x @ w
        return np.sum(np.exp(z) - y * z) + 0.5 * l2 * np.sum(w * w)

    ref = scipy.optimize.minimize(np_obj, np.zeros(d), method="L-BFGS-B",
                                  tol=1e-12).x
    for solve in (
        lambda: lbfgs_solve(lambda w: obj.value_and_gradient(w, batch),
                            jnp.zeros(d, jnp.float32), CFG),
        lambda: tron_solve(lambda w: obj.value_and_gradient(w, batch),
                           lambda w, v: obj.hessian_vector(w, v, batch),
                           jnp.zeros(d, jnp.float32), CFG),
    ):
        res = solve()
        assert bool(res.converged)
        np.testing.assert_allclose(res.w, ref, rtol=1e-3, atol=1e-4)


def test_owlqn_l1_logistic_matches_sklearn(rng):
    n, d, l1 = 400, 12, 3.0
    x = rng.normal(0, 1, (n, d))
    w_true = np.zeros(d)
    w_true[:3] = [2.0, -1.5, 1.0]  # sparse ground truth
    p = 1 / (1 + np.exp(-(x @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float64)
    batch = make_dense_batch(x, y)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.none(),  # L1 passed to the solver
        norm=NormalizationContext.identity(),
    )
    res = owlqn_solve(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros(d, jnp.float32),
        l1_weight=jnp.asarray(l1, jnp.float32),
        config=OptimizerConfig(max_iters=500, tolerance=1e-7),
    )
    clf = LogisticRegression(
        penalty="l1", C=1.0 / l1, solver="liblinear", fit_intercept=False,
        tol=1e-10, max_iter=10000,
    )
    clf.fit(x, y)
    w_ref = clf.coef_.ravel()
    np.testing.assert_allclose(res.w, w_ref, rtol=5e-2, atol=5e-3)
    # OWL-QN must produce exact zeros where sklearn does.
    assert np.all((np.abs(np.asarray(res.w)) < 1e-6) == (np.abs(w_ref) < 1e-6))


def test_elastic_net_poisson_via_problem(rng):
    """BASELINE config 3 shape: Poisson + elastic net through the problem API."""
    n, d = 300, 8
    x = rng.normal(0, 0.4, (n, d))
    lam = np.exp(x @ rng.normal(0, 0.5, d))
    y = rng.poisson(lam).astype(np.float64)
    batch = make_dense_batch(x, y)
    weight, alpha = 2.0, 0.5
    obj = GLMObjective(
        loss=losses.POISSON,
        reg=RegularizationContext.elastic_net(weight, alpha),
        norm=NormalizationContext.identity(),
    )
    problem = OptimizationProblem(
        objective=obj, optimizer=OptimizerType.LBFGS,
        config=OptimizerConfig(max_iters=500, tolerance=1e-6),
    )
    res = problem.run(batch, jnp.zeros(d, jnp.float32))

    l1_w, l2_w = alpha * weight, (1 - alpha) * weight

    def np_obj(w):
        z = x @ w
        return (np.sum(np.exp(z) - y * z) + 0.5 * l2_w * np.sum(w * w)
                + l1_w * np.sum(np.abs(w)))

    # scipy can't do L1 directly; check optimality by subgradient: for
    # nonzero coords grad_smooth + l1·sign(w) ≈ 0, for zeros |grad| ≤ l1.
    w = np.asarray(res.w, np.float64)
    z = x @ w
    g = x.T @ (np.exp(z) - y) + l2_w * w
    nz = np.abs(w) > 1e-6
    np.testing.assert_allclose(g[nz] + l1_w * np.sign(w[nz]), 0, atol=5e-3)
    assert np.all(np.abs(g[~nz]) <= l1_w + 5e-3)
    # And beats the zero vector.
    assert np_obj(w) < np_obj(np.zeros(d))


def test_vmap_per_problem_convergence(rng):
    """≥100 independent problems under one vmap, each at its own optimum."""
    B, n, d, l2 = 128, 40, 5, 0.3
    xs = rng.normal(0, 1, (B, n, d))
    ws = rng.normal(0, 1, (B, d))
    ps = 1 / (1 + np.exp(-np.einsum("bnd,bd->bn", xs, ws)))
    ys = (rng.uniform(size=(B, n)) < ps).astype(np.float64)

    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )
    cfg = OptimizerConfig(max_iters=150, tolerance=1e-6, track_states=False)

    def solve_one(x, y):
        batch = jax.tree.map(jnp.asarray, _as_batch(x, y))
        return lbfgs_solve(
            lambda w: obj.value_and_gradient(w, batch),
            jnp.zeros(d, jnp.float32), cfg,
        )

    def _as_batch(x, y):
        from photon_ml_tpu.data.batch import DenseBatch
        n_ = x.shape[0]
        return DenseBatch(
            x=x.astype(jnp.float32), labels=y.astype(jnp.float32),
            weights=jnp.ones(n_, jnp.float32),
            offsets=jnp.zeros(n_, jnp.float32),
            mask=jnp.ones(n_, jnp.float32),
        )

    res = jax.jit(jax.vmap(solve_one))(
        jnp.asarray(xs, jnp.float32), jnp.asarray(ys, jnp.float32)
    )
    assert bool(jnp.all(res.converged))
    # Iteration counts must differ across lanes (per-lane convergence, not
    # run-to-max): with 128 random problems identical counts would mean the
    # masked-while semantics are broken.
    assert len(np.unique(np.asarray(res.iterations))) > 1

    for b in range(0, B, 17):  # spot-check lanes against sklearn
        w_ref = _sklearn_logistic(xs[b], ys[b], l2)
        np.testing.assert_allclose(res.w[b], w_ref, rtol=5e-3, atol=1e-3)


def test_tron_vmap_converges(rng):
    B, n, d = 64, 30, 4
    xs = rng.normal(0, 1, (B, n, d))
    ys = (rng.uniform(size=(B, n)) < 0.5).astype(np.float64)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    cfg = OptimizerConfig(max_iters=100, tolerance=1e-6, track_states=False)

    from photon_ml_tpu.data.batch import DenseBatch

    def solve_one(x, y):
        n_ = x.shape[0]
        batch = DenseBatch(
            x=x, labels=y, weights=jnp.ones(n_, jnp.float32),
            offsets=jnp.zeros(n_, jnp.float32),
            mask=jnp.ones(n_, jnp.float32),
        )
        return tron_solve(
            lambda w: obj.value_and_gradient(w, batch),
            lambda w, v: obj.hessian_vector(w, v, batch),
            jnp.zeros(d, jnp.float32), cfg,
        )

    res = jax.jit(jax.vmap(solve_one))(
        jnp.asarray(xs, jnp.float32), jnp.asarray(ys, jnp.float32)
    )
    assert bool(jnp.all(res.converged))
    w_ref = _sklearn_logistic(xs[0], ys[0], 1.0)
    np.testing.assert_allclose(res.w[0], w_ref, rtol=5e-3, atol=1e-3)


def test_tracker_records_monotone_history(rng):
    x, y, batch, obj = _logistic_problem(rng)
    res = lbfgs_solve(
        lambda w: obj.value_and_gradient(w, batch),
        jnp.zeros(x.shape[1], jnp.float32),
        OptimizerConfig(max_iters=50, tolerance=1e-6),
    )
    k = int(res.tracker.count)
    vals = np.asarray(res.tracker.values)[:k]
    assert k == int(res.iterations) + 1
    assert np.all(np.isfinite(vals))
    assert np.all(np.diff(vals) <= 1e-6)  # non-increasing loss
    assert np.all(np.isnan(np.asarray(res.tracker.values)[k:]))


def test_tracker_records_step_sizes_and_trials(rng):
    """ISSUE 8: the tracker's per-iteration step-size and line-search
    trial planes are populated by both resident solvers (TRON records
    the step norm and inner-CG iteration count)."""
    x, y, batch, obj = _logistic_problem(rng)
    w0 = jnp.zeros(x.shape[1], jnp.float32)
    cfg = OptimizerConfig(max_iters=50, tolerance=1e-6)
    res = lbfgs_solve(lambda w: obj.value_and_gradient(w, batch), w0, cfg)
    k = int(res.tracker.count)
    assert k >= 2
    steps = np.asarray(res.tracker.step_sizes)
    trials = np.asarray(res.tracker.ls_trials)
    # Slot 0 is the initial point: no step taken there.
    assert np.isnan(steps[0]) and np.isnan(trials[0])
    assert np.all(np.isfinite(steps[1:k])) and np.all(steps[1:k] >= 0)
    assert np.all(trials[1:k] >= 1)
    # Accepted α=1 full steps dominate a well-conditioned logistic fit.
    assert np.any(steps[1:k] == 1.0)

    res_t = tron_solve(
        lambda w: obj.value_and_gradient(w, batch),
        lambda w, v: obj.hessian_vector(w, v, batch), w0, cfg)
    kt = int(res_t.tracker.count)
    steps_t = np.asarray(res_t.tracker.step_sizes)[1:kt]
    cg_t = np.asarray(res_t.tracker.ls_trials)[1:kt]
    assert np.all(np.isfinite(steps_t)) and np.all(steps_t >= 0)
    assert np.all(cg_t >= 1)              # every outer iter paid CG work


def test_tron_rejects_l1():
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l1(0.5),
        norm=NormalizationContext.identity(),
    )
    problem = OptimizationProblem(objective=obj, optimizer=OptimizerType.TRON)
    batch = make_dense_batch(np.zeros((2, 3)), np.zeros(2))
    with pytest.raises(ValueError, match="smooth"):
        problem.run(batch, jnp.zeros(3, jnp.float32))


def test_weighted_examples_shift_solution(rng):
    """Example weights must act as replication (reference weight semantics)."""
    n, d = 100, 4
    x = rng.normal(0, 1, (n, d))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    w3 = np.ones(n)
    w3[: n // 2] = 3.0
    batch_w = make_dense_batch(x, y, weights=w3)
    x_rep = np.concatenate([x[: n // 2]] * 3 + [x[n // 2:]])
    y_rep = np.concatenate([y[: n // 2]] * 3 + [y[n // 2:]])
    batch_rep = make_dense_batch(x_rep, y_rep)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    r1 = lbfgs_solve(lambda w: obj.value_and_gradient(w, batch_w),
                     jnp.zeros(d, jnp.float32), CFG)
    r2 = lbfgs_solve(lambda w: obj.value_and_gradient(w, batch_rep),
                     jnp.zeros(d, jnp.float32), CFG)
    np.testing.assert_allclose(r1.w, r2.w, atol=1e-3)


def test_boundary_tau_nonnegative_at_f32_boundary_crossing():
    """ISSUE 17 hardening: when ‖p‖ crosses Δ by one f32 rounding step
    (gap = Δ² − ‖p‖² negative by an ulp) while p·d > 0, the textbook
    root (−p·d + √disc)/(d·d) cancels catastrophically and returns a
    small NEGATIVE τ — a backward step that "exits" the trust region
    from inside while the CG loop reports a boundary hit.  The
    conjugate-root form plus the final clamp must return τ ≥ 0 with no
    NaN."""
    from photon_ml_tpu.optim.tron import _boundary_tau

    delta = jnp.float32(1.0)
    p = jnp.asarray([1.0 + 1.2e-7, 0.0], jnp.float32)  # ‖p‖ > Δ by ~1 ulp
    d = jnp.asarray([1.0, 1e-4], jnp.float32)          # p·d > 0
    tau = float(_boundary_tau(p, d, delta))
    assert np.isfinite(tau)
    assert tau >= 0.0
    assert tau < 1e-6   # the true root is within rounding of zero


def test_boundary_tau_roots_and_degenerate_direction():
    """Both quadratic branches return the exact boundary crossing, and
    a zero direction (the d·d floor) stays finite and non-negative."""
    from photon_ml_tpu.optim.tron import _boundary_tau

    delta = jnp.float32(1.0)
    # Forward crossing from inside (p·d > 0): 0.5 + τ = 1 → τ = 0.5.
    tau = float(_boundary_tau(jnp.asarray([0.5, 0.0], jnp.float32),
                              jnp.asarray([1.0, 0.0], jnp.float32),
                              delta))
    np.testing.assert_allclose(tau, 0.5, rtol=1e-6)
    # Backward direction (p·d < 0): 0.5 − τ = −1 → τ = 1.5.
    tau = float(_boundary_tau(jnp.asarray([0.5, 0.0], jnp.float32),
                              jnp.asarray([-1.0, 0.0], jnp.float32),
                              delta))
    np.testing.assert_allclose(tau, 1.5, rtol=1e-6)
    tau = float(_boundary_tau(jnp.asarray([0.5, 0.0], jnp.float32),
                              jnp.zeros(2, jnp.float32), delta))
    assert np.isfinite(tau) and tau >= 0.0


# -- the line search along the margins (ISSUE 31) ---------------------------
# A GLM's margins are affine in w, so a solve handed the objective split
# at its margins contracts X.d once an iteration and scores every trial
# on m + a.X.d.  It is the same algorithm as the solve by whole
# evaluations: in float64 (rounding out of the way) both make the same
# decisions.  What float32 rounding does to the carried margins is held
# to float64 in tests/test_grr_tail.py.

LOSSES = {"logistic": losses.LOGISTIC, "poisson": losses.POISSON,
          "squared": losses.SQUARED}


def _labels(rng, loss, z):
    if loss == "logistic":
        return (rng.uniform(size=z.shape) < 1 / (1 + np.exp(-z))).astype(
            np.float64)
    if loss == "poisson":
        return rng.poisson(np.exp(np.clip(z, -3.0, 2.0))).astype(np.float64)
    return z + rng.normal(size=z.shape)


@pytest.fixture(scope="module")
def kdd12_rows():
    """The fixed effect of ``game5-kdd12`` at its rehearsal size, with
    the intercept: (rows, dim, labels)."""
    from test_grr_tail import _ell, _rehearsal

    train, _valid, _truth = _rehearsal("game5-kdd12")
    rows, _cols, _vals, dim = _ell(train)
    return rows, dim, np.asarray(train.labels, np.float64)


def _dressed_objective(rng, loss, dim):
    """Factor-and-shift normalization and a Gaussian prior, float64."""
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, dim)),
        shifts=jnp.asarray(rng.normal(0, 0.1, dim)))
    prior = GaussianPrior(means=jnp.asarray(rng.normal(0, 0.1, dim)),
                          precisions=jnp.asarray(rng.uniform(0.5, 2.0, dim)),
                          weight=jnp.asarray(0.7))
    return GLMObjective(loss=LOSSES[loss], reg=RegularizationContext.l2(1.0),
                        norm=norm, prior=prior)


def _counting(fn, counter):
    """``fn`` with every execution (not every trace) counted."""
    def counted(*args):
        jax.debug.callback(lambda: counter.append(1))
        return fn(*args)
    return counted


@pytest.mark.parametrize("layout", ["dense", "ell", "grr_tail"])
@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_search_along_margins_is_the_search_by_whole_evaluations(
        rng, loss, layout, kdd12_rows, monkeypatch):
    from photon_ml_tpu.data import grr
    from photon_ml_tpu.data.batch import make_sparse_batch
    from photon_ml_tpu.optim.problem import as_margin_split

    if layout == "dense":
        n, dim = 300, 12
        x = rng.normal(size=(n, dim))
        y = _labels(rng, loss, x @ rng.normal(0, 0.3, dim))
        batch = make_dense_batch(x, y, weights=rng.uniform(0.5, 2.0, n),
                                 offsets=rng.normal(0, 0.5, n),
                                 dtype=jnp.float64)
    else:
        rows, dim, y = kdd12_rows
        n = len(y)
        if loss == "squared":
            y = y + rng.normal(size=n)
        # one entry does not pay for a column's slots: a tail at 6,000
        # rows, as at the cell's 3.185e6 (tests/test_grr_tail.py)
        monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)
        batch = make_sparse_batch(
            rows, dim, y, weights=rng.uniform(0.5, 2.0, n),
            offsets=rng.normal(0, 0.5, n), dtype=jnp.float64,
            grr=layout == "grr_tail", keep_ell=layout == "ell")
        assert (batch.grr is not None and batch.grr.tail.nnz > 0) \
            == (layout == "grr_tail")
    obj = _dressed_objective(rng, loss, dim)
    # Stops well above float64's noise floor, where an Armijo test can
    # fall either way; and after 10 iterations, because L-BFGS on the
    # wide input multiplies a difference of one rounding by ten to a
    # hundred an iteration (squared loss, ELL: 7e-17 of w after 2
    # iterations, 2e-11 after 5, 6e-10 after 10, 1e-5 after 30).
    cfg = OptimizerConfig(max_iters=10, tolerance=1e-6)
    w0 = jnp.zeros(dim, jnp.float64)

    forward_along, forward_whole = [], []

    def along(b, w):
        split = as_margin_split(obj, b)
        return lbfgs_solve(split._replace(
            margins=_counting(split.margins, forward_along),
            margin_step=_counting(split.margin_step, forward_along)),
            w, cfg)

    def whole(b, w):
        return lbfgs_solve(_counting(
            lambda v: obj.value_and_gradient(v, b), forward_whole), w, cfg)

    got = jax.jit(along)(batch, w0)
    want = jax.jit(whole)(batch, w0)
    jax.effects_barrier()

    iterations = int(want.iterations)
    assert int(got.iterations) == iterations >= 10
    np.testing.assert_array_equal(got.tracker.ls_trials,
                                  want.tracker.ls_trials)
    trials = int(np.nansum(np.asarray(want.tracker.ls_trials)))
    assert trials > iterations           # some step was backtracked
    scale = float(jnp.max(jnp.abs(want.w)))
    assert float(jnp.max(jnp.abs(got.w - want.w))) <= 1e-5 * scale
    assert float(jnp.abs(got.value - want.value)) \
        <= 1e-9 * float(jnp.abs(want.value))
    # what each solve says it made, and what it made
    assert int(got.forward_passes) == iterations + 1 == len(forward_along)
    assert got.ls_trials is None      # a trial there is no contraction
    assert int(want.ls_trials) == trials
    assert int(want.forward_passes) == iterations + 1 + trials \
        == len(forward_whole)


@pytest.mark.parametrize("loss,l1", [("poisson", False), ("logistic", True),
                                     ("poisson", True)])
def test_search_under_vmap_each_lane_is_its_solo_solve(rng, loss, l1):
    """Lanes whose searches take different numbers of trials: the
    margins of a lane that has accepted wait, like its w, while the
    others backtrack: those it walked to along the step, or with an L1
    term (ISSUE 38) those its last trial kept."""
    lanes, n, dim = 6, 60, 5
    xs = rng.normal(size=(lanes, n, dim)) * rng.uniform(
        0.5, 6.0, (lanes, 1, 1))
    ys = np.stack([_labels(rng, loss, x @ rng.normal(0, 0.3, dim))
                   for x in xs])
    batches = jax.vmap(lambda x, y, o: jax.tree.map(
        jnp.asarray, make_dense_batch(x, y, offsets=o, dtype=jnp.float64))
    )(xs, ys, rng.normal(0, 0.5, (lanes, n)))
    reg = (RegularizationContext.elastic_net(1.0, 0.5) if l1
           else RegularizationContext.l2(0.5))
    problem = OptimizationProblem(
        objective=GLMObjective(loss=LOSSES[loss], reg=reg,
                               norm=NormalizationContext.identity()),
        config=OptimizerConfig(max_iters=40, tolerance=1e-6))
    w0s = jnp.zeros((lanes, dim), jnp.float64)
    run = partial(problem.run, has_l1=l1)
    together = jax.jit(jax.vmap(run))(batches, w0s)
    trials = np.nansum(np.asarray(together.tracker.ls_trials), axis=1)
    assert len(set(trials.tolist())) > 2
    for lane in range(lanes):
        solo = jax.jit(run)(jax.tree.map(lambda a: a[lane], batches),
                            w0s[lane])
        assert int(solo.iterations) == int(together.iterations[lane])
        if l1:
            # batched, a lane walks nothing and every trial contracts;
            # alone, the trials the projection clips nothing of walk the
            # margins, after one X·d in their search
            assert together.walked_trials is None
            assert int(together.forward_passes[lane]) == 1 + int(trials[lane])
            walked = int(solo.walked_trials)
            xd_searches = int(solo.forward_passes) - (
                1 + int(trials[lane]) - walked)
            assert 1 <= xd_searches <= min(walked, int(solo.iterations))
        else:
            assert int(solo.forward_passes) == int(
                together.forward_passes[lane]) == 1 + int(solo.iterations)
        np.testing.assert_array_equal(solo.tracker.ls_trials,
                                      together.tracker.ls_trials[lane])
        np.testing.assert_allclose(together.w[lane], solo.w, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(solo.w))))


# -- OWL-QN keeps its last trial's margins (ISSUE 38) ------------------------
# The orthant projection bends the step, so a trial's margins are
# contracted from its own point; but handed the split, the search keeps
# those of the trial it ends on and the accepted point's gradient is taken
# from them: 1 + ls_trials forward contractions where a bare callable,
# which shows no margins, pays 1 + ls_trials + iterations.

# sha256 of the StableHLO text ``_owlqn_from_callable`` lowers to with
# ``_l1_problem(rng, "logistic", float64)``, read on the parent commit
# 02da805 (``git archive`` into a scratch directory) and on this tree: a
# bare callable's OWL-QN is the parent's program.
PARENT_OWLQN_FROM_A_CALLABLE = (
    "226df47ae6cbb5f5d04012295e3b1449ff8ecaf55e24ec74d2d1f2f4c1ae473c")


def _l1_problem(rng, loss, dtype, n=200, dim=8):
    """A dressed objective of ``loss`` over a dense batch with weights
    and an offset, and the L1 weights to solve it with."""
    x = rng.normal(size=(n, dim)) * rng.uniform(0.5, 3.0, dim)
    y = _labels(rng, loss, x @ rng.normal(0, 0.3, dim))
    batch = make_dense_batch(x, y, weights=rng.uniform(0.5, 2.0, n),
                             offsets=rng.normal(0, 0.5, n), dtype=dtype)
    obj = jax.tree.map(lambda a: a.astype(dtype),
                       _dressed_objective(rng, loss, dim))
    return obj, batch, jnp.full(dim, 0.3, dtype).at[0].set(0.0)


def _owlqn_from_callable(cfg, calls=None):
    def solve(obj, batch, w0, l1):
        vg = lambda v: obj.value_and_gradient(v, batch)
        return lbfgs_solve(vg if calls is None else _counting(vg, calls),
                           w0, cfg, l1_weight=l1)
    return solve


def _owlqn_from_split(cfg, margins=None, margin_step=None, gradient=None):
    """``gradient(m, w)`` is called with the arguments of every
    ``value_and_grad`` the solve executes."""
    from photon_ml_tpu.optim.problem import as_margin_split

    def solve(obj, batch, w0, l1):
        split = as_margin_split(obj, batch)
        if margins is not None:
            split = split._replace(
                margins=_counting(split.margins, margins),
                margin_step=_counting(split.margin_step, margin_step))
        if gradient is not None:
            whole = split.value_and_grad

            def seen(m, w):
                jax.debug.callback(gradient, m, w)
                return whole(m, w)
            split = split._replace(value_and_grad=seen)
        return lbfgs_solve(split, w0, cfg, l1_weight=l1)
    return solve


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_an_l1_solve_through_the_split_is_the_solve_of_the_bare_callable(
        rng, dtype):
    """The same trial points, projection and Armijo test, and what they
    reach; what differs is what each pays for it."""
    obj, batch, l1 = _l1_problem(rng, "logistic", dtype)
    cfg = OptimizerConfig(max_iters=9, tolerance=1e-6)
    w0 = jnp.zeros(8, dtype)
    whole_calls, margins_calls, step_calls = [], [], []

    # (i) a bare callable: the parent's program and the parent's count
    if dtype == jnp.float64:
        text = jax.jit(_owlqn_from_callable(cfg)).lower(
            obj, batch, w0, l1).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() \
            == PARENT_OWLQN_FROM_A_CALLABLE
    want = jax.jit(_owlqn_from_callable(cfg, whole_calls))(obj, batch, w0, l1)
    # (ii) the split: a contraction at the start and one a trial the
    # projection clipped, one X·d for each search that walked a trial
    got = jax.jit(_owlqn_from_split(cfg, margins_calls, step_calls))(
        obj, batch, w0, l1)
    jax.effects_barrier()
    iterations = int(want.iterations)
    assert iterations > 0 and int(want.ls_trials) > iterations
    assert int(want.forward_passes) \
        == 1 + int(want.ls_trials) + iterations == len(whole_calls)
    walked = int(got.walked_trials)
    assert int(got.forward_passes) == len(margins_calls) + len(step_calls)
    assert len(margins_calls) == 1 + int(got.ls_trials) - walked
    assert 1 <= len(step_calls) <= min(walked, iterations)
    assert int(got.ls_trials) == int(
        np.nansum(np.asarray(got.tracker.ls_trials)))

    # (iii) and they reach the same point
    if dtype == jnp.float64:
        assert int(got.iterations) == iterations
        np.testing.assert_array_equal(got.tracker.ls_trials,
                                      want.tracker.ls_trials)
    tight = 1e-5 if dtype == jnp.float32 else 1e-9
    scale = float(jnp.max(jnp.abs(want.w)))
    assert float(jnp.max(jnp.abs(got.w - want.w))) <= tight * scale
    assert float(jnp.abs(got.value - want.value)) \
        <= tight * float(jnp.abs(want.value))


@pytest.mark.parametrize("loss", ["logistic", "poisson"])
def test_owlqn_takes_each_accepted_gradient_from_the_point_s_own_margins(
        rng, loss):
    """Every gradient the solve takes is handed the margins of that very
    point, and the result's is the one recomputed from its w."""
    from photon_ml_tpu.optim.lbfgs import _pseudo_gradient

    obj, batch, l1 = _l1_problem(rng, loss, jnp.float64)
    cfg = OptimizerConfig(max_iters=8, tolerance=0.0)
    taken = []
    res = jax.jit(_owlqn_from_split(
        cfg, gradient=lambda m, w: taken.append((m, w))))(
        obj, batch, jnp.zeros(8, jnp.float64), l1)
    jax.effects_barrier()
    assert len(taken) == 1 + int(res.iterations) == 9
    for m, w in taken:
        np.testing.assert_allclose(m, obj.margins(jnp.asarray(w), batch),
                                   rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(taken[-1][1], res.w)
    g = obj.value_and_gradient(res.w, batch)[1]
    np.testing.assert_allclose(
        res.grad_norm, jnp.linalg.norm(_pseudo_gradient(g, res.w, l1)),
        rtol=1e-10)


@pytest.mark.parametrize("loss", ["logistic", "poisson"])
def test_owlqn_search_that_exhausts_its_steps_stays_at_the_old_point(
        rng, loss, monkeypatch):
    """Allowed three trials a search, the solve soon meets one it cannot
    end (the examples weigh a thousandth, so that the first step, of
    steepest descent, is not that one): it stops there, and its carry
    (w, f, g, margins) is the one a solve capped an iteration earlier
    ends with, though the rejected trial's margins went through
    ``accept``."""
    from photon_ml_tpu.optim import lbfgs

    obj, batch, l1 = _l1_problem(rng, loss, jnp.float64)
    batch = batch.replace(weights=batch.weights * 1e-3)
    carries = []
    whole_loop = jax.lax.while_loop

    def spying(cond, body, init):
        out = whole_loop(cond, body, init)
        if isinstance(out, lbfgs._LbfgsCarry):
            carries.append(out)
        return out

    monkeypatch.setattr(lbfgs.jax.lax, "while_loop", spying)
    w0 = jnp.zeros(8, jnp.float64)

    def solve(max_iters):
        return _owlqn_from_split(OptimizerConfig(
            max_iters=max_iters, tolerance=0.0, ls_max_steps=2))(
            obj, batch, w0, l1 * 1e-3)

    stalled = solve(30)
    iterations = int(stalled.iterations)
    assert 1 < iterations < 30 and bool(stalled.converged)
    assert float(stalled.tracker.step_sizes[iterations]) == 0.0
    assert int(stalled.tracker.ls_trials[iterations]) == 3
    solve(iterations - 1)
    rejected, old = carries
    for name in ("w", "f", "g", "margins"):
        np.testing.assert_array_equal(getattr(rejected, name),
                                      getattr(old, name))
    np.testing.assert_allclose(old.margins, obj.margins(old.w, batch),
                               rtol=1e-12, atol=1e-12)
    # its three trials were made all the same: a contraction each, or,
    # where the projection clipped nothing, a walk after one X·d
    walked = int(rejected.walked_trials) - int(old.walked_trials)
    assert int(rejected.ls_trials) == int(old.ls_trials) + 3
    assert int(rejected.forward_passes) \
        == int(old.forward_passes) + 3 - walked + (walked > 0)
