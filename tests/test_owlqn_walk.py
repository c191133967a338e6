"""OWL-QN walks the margins of the trials its projection clips nothing of.

A trial of OWL-QN's line search is ``π(w + a·d; ξ)``: where the orthant
projection clips no coordinate it is the straight step, whose margins are
``m + a·X·d``.  Through a ``MarginSplit`` the search scores such trials
along the margins, ``X·d`` contracted by the first of them and handed on,
and contracts only the trials the projection clipped.  From w = 0 nothing
can cross zero, so the first search walks every trial; there the step is a
power of two and ``o + a·X·d`` is the contraction ``X·(a·d) + o`` to the
bit.  The parent algorithm, a contraction a trial, is kept here as the
oracle.  The other solvers' programs are the parent's.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.batch import make_dense_batch, make_sparse_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.game import coordinates
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim import OptimizerConfig, lbfgs, lbfgs_solve, tron_solve
from photon_ml_tpu.optim.lbfgs import lbfgs_solve_swept
from photon_ml_tpu.optim.problem import as_margin_split
from test_optim import _counting, _labels

LOSSES = {"logistic": losses.LOGISTIC, "poisson": losses.POISSON}


def _parent_keeping_margins(split, l1_vec):
    """The parent's OWL-QN through the split: every trial contracts its
    own point, and the search keeps the margins of the last."""

    def open_search(c, d):
        def trial(alpha, w_try, kept):
            m_try = split.margins(w_try)
            return (split.value(m_try, w_try)
                    + jnp.sum(l1_vec * jnp.abs(w_try))), m_try

        def accept(alpha, w_new, trials, m_new):
            return (m_new, (c.forward_passes + trials, None, None),
                    split.value_and_grad(m_new, w_new)[1])

        return trial, accept

    return lbfgs._start_at_margins(split), open_search


def _wide_problem(loss, seed=0, n=1000, dim=2000, k=8):
    """A sparse float32 elastic net shaped like the wide cells: few
    features a row, most coefficients held at zero, and every search
    after the first clips some coordinate."""
    rng = np.random.default_rng(seed)
    rows = [(np.sort(rng.choice(dim, k, replace=False)).astype(np.int32),
             np.ones(k)) for _ in range(n)]
    beta = rng.normal(0, 1.0, dim) * (rng.uniform(size=dim) < 0.2)
    z = np.array([beta[cols].sum() for cols, _ in rows]) - 1.0
    batch = make_sparse_batch(rows, dim, _labels(rng, loss, z),
                              offsets=rng.normal(0, 0.3, n),
                              dtype=jnp.float32)
    obj = GLMObjective(loss=LOSSES[loss], reg=RegularizationContext.l2(0.1),
                       norm=NormalizationContext.identity())
    return obj, batch, jnp.full(dim, 1.0, jnp.float32)


def _dense_problem(loss, seed=1, n=300, dim=10):
    """A dense float64 elastic net: a trial scored at the wrong margins
    would show in every row."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)) * rng.uniform(0.5, 3.0, dim)
    y = _labels(rng, loss, x @ rng.normal(0, 0.3, dim))
    batch = make_dense_batch(x, y, weights=rng.uniform(0.5, 2.0, n),
                             offsets=rng.normal(0, 0.5, n),
                             dtype=jnp.float64)
    obj = GLMObjective(loss=LOSSES[loss], reg=RegularizationContext.l2(0.5),
                       norm=NormalizationContext.identity())
    return obj, batch, jnp.full(dim, 2.0, jnp.float64).at[0].set(0.0)


def _split_solve(obj, cfg, l1, margins=None, margin_step=None, scored=None):
    """A fresh jitted OWL-QN through the split (a new function, so that a
    patched mode is traced anew); the callbacks see what it executes."""
    def solve(batch, w0):
        split = as_margin_split(obj, batch)
        if margins is not None:
            split = split._replace(
                margins=_counting(split.margins, margins),
                margin_step=_counting(split.margin_step, margin_step))
        if scored is not None:
            value = split.value

            def seen(m, w):
                jax.debug.callback(scored, m, w)
                return value(m, w)
            split = split._replace(value=seen)
        return lbfgs_solve(split, w0, cfg, l1_weight=l1)
    return jax.jit(solve)


@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_a_walk_from_zero_is_the_parent_s_solve_bit_for_bit(
        loss, monkeypatch):
    obj, batch, l1 = _wide_problem(loss)
    cfg = OptimizerConfig(max_iters=12, tolerance=0.0)
    w0 = jnp.zeros(batch.dim, jnp.float32)
    got = _split_solve(obj, cfg, l1)(batch, w0)
    monkeypatch.setattr(lbfgs, "_keeping_margins", _parent_keeping_margins)
    want = _split_solve(obj, cfg, l1)(batch, w0)

    assert want.walked_trials is None
    first = int(want.tracker.ls_trials[1])
    assert first > 1                  # the first search backtracked
    assert int(got.walked_trials) == first
    assert int(got.iterations) == int(want.iterations) == 12
    assert int(got.ls_trials) == int(want.ls_trials)
    np.testing.assert_array_equal(got.tracker.ls_trials,
                                  want.tracker.ls_trials)
    np.testing.assert_array_equal(got.w, want.w)
    assert got.value.tobytes() == want.value.tobytes()
    np.testing.assert_array_equal(coordinates._score_batch(batch, got.w),
                                  coordinates._score_batch(batch, want.w))
    # the first search's trials cost one X·d, not one contraction each
    assert int(want.forward_passes) == 1 + int(want.ls_trials)
    assert int(got.forward_passes) == int(want.forward_passes) - first + 1


@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_a_warm_start_walks_only_the_trials_that_clip_nothing(loss):
    """From w0 ≠ 0 some searches clip and some do not.  Every trial is
    scored at the margins of its own point (a clipped trial walked would
    be off them), the contractions are the trials not walked, and the
    solve reaches the bare callable's."""
    obj, batch, l1 = _dense_problem(loss)
    cfg = OptimizerConfig(max_iters=15, tolerance=0.0)
    w0 = jnp.asarray(np.random.default_rng(2).normal(0, 0.3, batch.x.shape[1]))
    margins_calls, step_calls, trials = [], [], []
    got = _split_solve(obj, cfg, l1, margins_calls, step_calls,
                       lambda m, w: trials.append((m, w)))(batch, w0)
    want = jax.jit(lambda b, w: lbfgs_solve(
        lambda v: obj.value_and_gradient(v, b), w, cfg, l1_weight=l1))(
            batch, w0)
    jax.effects_barrier()

    walked = int(got.walked_trials)
    assert len(trials) == int(got.ls_trials)
    contracted = len(margins_calls) - 1
    assert contracted == int(got.ls_trials) - walked
    assert walked > 0 and contracted > 0 and len(step_calls) > 0
    for m, w in trials:
        np.testing.assert_allclose(m, obj.margins(jnp.asarray(w), batch),
                                   rtol=1e-12, atol=1e-12)
    scale = float(jnp.max(jnp.abs(want.w)))
    assert float(jnp.max(jnp.abs(got.w - want.w))) <= 1e-5 * scale
    assert float(jnp.abs(got.value - want.value)) \
        <= 1e-5 * float(jnp.abs(want.value))


@pytest.mark.parametrize("start", ["zero", "warm"])
@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_contractions_are_start_clipped_trials_and_one_x_d_a_walking_search(
        loss, start):
    """``forward_passes = 1 + ls_trials − walked_trials + the searches
    that contracted X·d``, each term counted where it was executed."""
    obj, batch, l1 = _dense_problem(loss)
    cfg = OptimizerConfig(max_iters=10, tolerance=0.0)
    dim = batch.x.shape[1]
    w0 = (jnp.zeros(dim, jnp.float64) if start == "zero" else
          jnp.asarray(np.random.default_rng(3).normal(0, 0.3, dim)))
    margins_calls, step_calls = [], []
    got = _split_solve(obj, cfg, l1, margins_calls, step_calls)(batch, w0)
    jax.effects_barrier()
    trials, walked = int(got.ls_trials), int(got.walked_trials)
    assert int(got.forward_passes) \
        == 1 + trials - walked + len(step_calls) \
        == len(margins_calls) + len(step_calls)
    assert len(step_calls) <= min(walked, int(got.iterations))
    assert trials == int(np.nansum(np.asarray(got.tracker.ls_trials)))
    if start == "zero":
        assert walked >= int(got.tracker.ls_trials[1]) and step_calls


# -- the programs the walk leaves as they were --------------------------------

# sha256 of the StableHLO text each case below lowers to, read on the
# parent commit e474493 (``git archive`` into a scratch directory) and on
# this tree: L-BFGS along the margins, OWL-QN of a bare callable, the
# swept lanes (vmapped and mapped) and TRON carry no walk.  OWL-QN
# through the split under vmap is ``test_tron_counts``' per-entity case.
PARENT_PROGRAMS = {
    "lbfgs_along_margins":
        "cdb7a06e8cdb8b238cedb84901293c50916cab90741def7c452b3c0753dca09f",
    "owlqn_bare_callable":
        "c864fab67dd40c9669d760dec1d4078737ee04548751fa9abb28e7f1b1f2184d",
    "owlqn_swept_vmap":
        "02c7ec2111a589b03772aea87c638d3a0c5186900429ae42e752de6d0836644d",
    "owlqn_swept_map":
        "007d91bee172ca604c3ce6766dcb00cde430c9fb494c8125d8192c93a67475aa",
    "tron":
        "934f5a4c272746ffdfabb3df99ebd7897f7fb59a700cfb7793616c8399d223de",
}


def _program(case):
    obj, batch, l1 = _dense_problem("logistic", n=40, dim=6)
    cfg = OptimizerConfig(max_iters=5, track_states=False)
    w0 = jnp.zeros(6, jnp.float64)
    vg = lambda w, b: obj.value_and_gradient(w, b)
    lanes = 3
    if case == "lbfgs_along_margins":
        fn = lambda b, w: lbfgs_solve(as_margin_split(obj, b), w, cfg)
        return jax.jit(fn).lower(batch, w0)
    if case == "owlqn_bare_callable":
        fn = lambda b, w: lbfgs_solve(lambda v: vg(v, b), w, cfg,
                                      l1_weight=l1)
        return jax.jit(fn).lower(batch, w0)
    if case.startswith("owlqn_swept"):
        # a lane's context scales its objective
        fn = lambda b, w: lbfgs_solve_swept(
            lambda v, s: jax.tree.map(lambda a: a * s, vg(v, b)), w,
            jnp.ones(lanes), cfg, l1_weights=jnp.stack([l1] * lanes),
            use_map=case == "owlqn_swept_map")
        return jax.jit(fn).lower(batch, jnp.stack([w0] * lanes))
    assert case == "tron"
    fn = lambda b, w: tron_solve(
        lambda v: vg(v, b), lambda v, u: obj.hessian_vector(v, u, b), w, cfg)
    return jax.jit(fn).lower(batch, w0)


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_the_programs_without_a_walk_are_the_parent_s(case):
    text = _program(case).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[case]
