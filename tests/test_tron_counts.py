"""What a TRON solve says it paid, counted in its carry whether or not
states are tracked: its CG steps, its Hessian-vector products (the CG
steps' and the ratio's one an outer iteration) and its forward
contractions X·v (the start, each outer iteration's evaluation at
w + p, and each product's two: X·w for the curvature and X·v), of which
XLA drops the squared loss's X·w.  Beside them: the inner loop's cap as
a configuration states it, and the L-BFGS and OWL-QN programs, which
the counts leave as they were."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import (
    OptimizerSettings,
    config_to_json,
    training_config_from_json,
)
from photon_ml_tpu.data.batch import make_dense_batch, make_sparse_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.game import coordinates
from photon_ml_tpu.game.coordinate_descent import _solve_counts
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim import OptimizationProblem, OptimizerConfig
from photon_ml_tpu.optim.base import OptimizerType

LOSSES = {"logistic": losses.LOGISTIC, "poisson": losses.POISSON,
          "squared": losses.SQUARED}


def _labels(rng, loss, margins):
    if loss == "logistic":
        return (rng.uniform(size=margins.shape)
                < 1 / (1 + np.exp(-margins))).astype(float)
    if loss == "poisson":
        return rng.poisson(np.exp(margins)).astype(float)
    return margins + rng.normal(0, 0.3, margins.shape)


def _problem(rng, loss, n=120, dim=6, **config):
    x = rng.normal(size=(n, dim))
    batch = make_dense_batch(x, _labels(rng, loss, x @ rng.normal(0, 0.4, dim)),
                             weights=rng.uniform(0.5, 3.0, n))
    problem = OptimizationProblem(
        objective=GLMObjective(loss=LOSSES[loss],
                               reg=RegularizationContext.l2(1.0),
                               norm=NormalizationContext.identity()),
        optimizer=OptimizerType.TRON,
        config=OptimizerConfig(**dict({"max_iters": 6, "tolerance": 1e-12,
                                       "cg_max_iters": 3}, **config)))
    return problem, batch


def _identities(result, loss):
    iterations = np.asarray(result.iterations)
    cg = np.asarray(result.cg_steps)
    hvp = np.asarray(result.hvp_passes)
    np.testing.assert_array_equal(hvp, cg + iterations)
    np.testing.assert_array_equal(
        np.asarray(result.forward_passes),
        1 + iterations + 2 * hvp)
    assert result.cg_steps.dtype == result.hvp_passes.dtype \
        == result.forward_passes.dtype == jnp.int32


@pytest.mark.parametrize("loss", sorted(LOSSES))
@pytest.mark.parametrize("track_states", [False, True])
def test_a_resident_solve_counts_what_it_paid(rng, loss, track_states):
    problem, batch = _problem(rng, loss, track_states=track_states)
    result = jax.jit(lambda b, w: problem.run(b, w))(
        batch, jnp.zeros(6, jnp.float32))
    _identities(result, loss)
    assert int(result.iterations) > 0 and int(result.cg_steps) > 0
    if track_states:   # the tracker's plane holds each iteration's CG steps
        assert int(np.nansum(np.asarray(result.tracker.ls_trials))) \
            == int(result.cg_steps)


@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_every_contraction_a_resident_solve_counts_is_made(rng, loss,
                                                          monkeypatch):
    """The count against the contractions the program makes: every X·v
    of the batch reports itself from the device (the callback keeps
    each one, the squared loss's X·w for its curvature too)."""
    from photon_ml_tpu.data.batch import DenseBatch

    made = []
    x_dot = DenseBatch.x_dot

    def counted(self, w):
        jax.debug.callback(lambda: made.append(1))
        return x_dot(self, w)

    monkeypatch.setattr(DenseBatch, "x_dot", counted)
    jax.clear_caches()
    problem, batch = _problem(rng, loss)
    result = jax.jit(lambda b, w: problem.run(b, w))(
        batch, jnp.zeros(6, jnp.float32))
    jax.effects_barrier()
    jax.clear_caches()
    assert int(result.forward_passes) == len(made)


def _compiled_gathers(loss):
    """The gathers X·v makes, in the optimized program of a fixed-effect
    TRON solve over a sparse batch (one a contraction)."""
    rng = np.random.default_rng(7)
    n, dim = 64, 12
    rows = [(np.sort(rng.choice(dim, 3, replace=False)).astype(np.int32),
             np.ones(3)) for _ in range(n)]
    batch = make_sparse_batch(rows, dim, rng.random(n), dtype=jnp.float32)
    obj = GLMObjective(loss=LOSSES[loss], reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext())
    text = coordinates._fixed_train_local.lower(
        OptimizerType.TRON, OptimizerConfig(max_iters=7, track_states=False),
        False, obj, batch, jnp.zeros(n, jnp.float32), None, None,
        jnp.zeros(dim, jnp.float32)).compile().as_text()
    return len(re.findall(r"= \S+ gather\(", text))


def test_xla_drops_the_squared_loss_s_dead_curvature_contraction():
    """The squared loss's d2 reads no margin, so the X·w a product makes
    for its curvature is dead and the compiled solve runs one X·v a
    product: the start, the evaluation at w + p, CG's X·d and the
    ratio's X·p.  A logistic product's X·w is live, in CG and in the
    ratio: two more."""
    assert _compiled_gathers("squared") == 4
    assert _compiled_gathers("logistic") == 6


@pytest.mark.parametrize("loss", sorted(LOSSES))
def test_vmapped_solves_count_lane_by_lane(rng, loss):
    """Per-entity solves: each lane's counts are its own, and a lane that
    stops early stops counting while the others go on."""
    problem, batch = _problem(rng, loss, max_iters=8, tolerance=1e-3)
    lanes = 5
    scale = jnp.asarray([0.2, 1.0, 3.0, 0.0, 10.0], jnp.float32)
    batches = jax.tree.map(lambda a: jnp.stack([a] * lanes), batch)
    batches = batches.replace(x=batches.x * scale[:, None, None],
                              mask=batches.mask.at[3].set(0.0))
    result = jax.jit(jax.vmap(problem.run))(
        batches, jnp.zeros((lanes, 6), jnp.float32))
    _identities(result, loss)
    for lane in range(lanes):
        alone = problem.run(jax.tree.map(lambda a: a[lane], batches),
                            jnp.zeros(6, jnp.float32))
        for key in ("iterations", "cg_steps", "hvp_passes",
                    "forward_passes"):
            assert int(getattr(result, key)[lane]) \
                == int(getattr(alone, key)), (lane, key)
    assert int(result.iterations[3]) == 0          # an empty entity
    assert int(result.forward_passes[3]) == 1
    assert len(set(np.asarray(result.hvp_passes).tolist())) > 1


def test_the_stage_carries_a_solve_s_counts_and_a_random_effect_s_sums(rng):
    problem, batch = _problem(rng, "squared", track_states=False)
    one = problem.run(batch, jnp.zeros(6, jnp.float32))
    counts = _solve_counts(one)
    assert counts == {"solver_iterations": int(one.iterations),
                      "cg_steps": int(one.cg_steps),
                      "hvp_passes": int(one.hvp_passes),
                      "forward_passes": int(one.forward_passes)}
    buckets = [jax.vmap(problem.run)(
        jax.tree.map(lambda a: jnp.stack([a] * k), batch),
        jnp.zeros((k, 6), jnp.float32)) for k in (2, 3)]
    assert _solve_counts(buckets) == {
        "lane_cg_steps": 5 * int(one.cg_steps),
        "lane_hvp_passes": 5 * int(one.hvp_passes),
        "lane_forward_passes": 5 * int(one.forward_passes)}
    # an L-BFGS random effect's stage carries nothing of the solver
    lbfgs = problem.replace(optimizer=OptimizerType.LBFGS)
    assert _solve_counts([jax.vmap(lbfgs.run)(
        jax.tree.map(lambda a: jnp.stack([a] * 2), batch),
        jnp.zeros((2, 6), jnp.float32))]) == {}


def test_the_inner_cap_binds(rng):
    problem, batch = _problem(rng, "logistic", cg_max_iters=1, max_iters=4,
                              tolerance=1e-12)
    result = problem.run(batch, jnp.zeros(6, jnp.float32))
    assert int(result.cg_steps) == int(result.iterations) == 4


# -- the inner loop's settings ---------------------------------------------------

def _training_json(**optimizer):
    return f"""{{"task_type": "LINEAR_REGRESSION",
        "coordinates": [{{"name": "global", "kind": "FIXED_EFFECT",
            "feature_shard": "global",
            "optimizer": {{"optimizer": "TRON", "max_iters": 5
            {"".join(f', "{k}": {v}' for k, v in optimizer.items())}}}}}],
        "update_sequence": ["global"]}}"""


def test_cg_settings_default_to_the_solver_s_and_round_trip():
    from photon_ml_tpu.estimators.game_estimator import _optimizer_config

    assert OptimizerSettings().cg_max_iters \
        == OptimizerConfig().cg_max_iters == 50
    config = training_config_from_json(_training_json())
    settings = config.coordinates[0].optimizer
    assert settings.cg_max_iters == 50
    assert _optimizer_config(settings) == OptimizerConfig(
        max_iters=5, tolerance=settings.tolerance, track_states=False)

    config = training_config_from_json(_training_json(cg_max_iters=8))
    settings = config.coordinates[0].optimizer
    assert settings.cg_max_iters == 8
    solver = _optimizer_config(settings)
    # the forcing tolerance stays the solver's own
    assert (solver.cg_max_iters, solver.cg_tolerance) == (8, 0.1)
    again = training_config_from_json(config_to_json(config))
    assert again.coordinates[0].optimizer == settings


@pytest.mark.parametrize("bad,word", [
    ({"cg_max_iters": 0}, "cg_max_iters"),
    ({"cg_max_iters": 2.5}, "cg_max_iters"),
    ({"cg_max_iters": "true"}, "cg_max_iters"),
    ({"cg_max_iters": -3}, "cg_max_iters"),
    ({"cg_tolerance": 0.05}, "cg_tolerance"),     # not a setting
])
def test_unsound_cg_settings_are_refused(bad, word):
    with pytest.raises(ValueError, match=word):
        training_config_from_json(_training_json(**bad))


# -- the other solvers' programs, as they were -------------------------------------

# sha256 of the StableHLO text each case below lowers to, read on the
# parent commit d8e7343 (``git archive`` into a scratch directory) and
# on this tree: TRON's counts leave the L-BFGS and OWL-QN programs, the
# fixed effect's and the per-entity ones, as they were.  One has moved
# since: the fixed effect's OWL-QN, whose trials that the orthant
# projection clips nothing of walk the margins (read on this tree; the
# per-entity OWL-QN lanes, batched, do not walk and kept theirs).
PARENT_PROGRAMS = {
    ("logistic", "lbfgs", "fixed"):
        "a91b8683382b4b35eac63b069a129f899d090b9ac71e93502f47b83f99c1f59c",
    ("logistic", "lbfgs", "entities"):
        "560c9580f24103bf851cf70cc6f84c15b70a1250063b60489e38ed014687600b",
    ("poisson", "owlqn", "fixed"):
        # the walk along m + a·X·d of the trials that clip nothing
        "3a75b58b60f2e907321744d7bc4ac92910b4eca07829ba712d508697d82ff0aa",
    ("poisson", "lbfgs", "entities"):
        "23d12ad364cfb83887700d9bbee0ea6a81871bd391b3fcb56336a6ef0aa47d38",
    ("logistic", "owlqn", "entities"):
        "b74b7203e01743d0f9fc0095fae7693cf64076edc848bdc59d567f55ca4b2c85",
}


def _lowered(loss, solver, where):
    """The StableHLO text of a fixed-effect solve (the coordinate's own
    jitted program, over a sparse batch) or of a vmapped per-entity
    solve, with float64 arrays as the tests' x64 gives them."""
    rng = np.random.default_rng(7)
    n, dim = 64, 12
    labels = (rng.random(n) < 0.3).astype(np.float64)
    reg = (RegularizationContext.elastic_net(1.0, 0.5, None)
           if solver == "owlqn" else RegularizationContext.l2(1.0))
    obj = GLMObjective(loss=LOSSES[loss], reg=reg,
                       norm=NormalizationContext())
    cfg = OptimizerConfig(max_iters=7, track_states=False)
    if where == "fixed":
        rows = [(np.sort(rng.choice(dim, 3, replace=False)).astype(np.int32),
                 np.ones(3)) for _ in range(n)]
        batch = make_sparse_batch(rows, dim, labels, dtype=jnp.float64)
        return coordinates._fixed_train_local.lower(
            OptimizerType.LBFGS, cfg, solver == "owlqn", obj, batch,
            jnp.zeros(n, jnp.float64), None, None,
            jnp.zeros(dim, jnp.float64)).as_text()
    batch = make_dense_batch(rng.normal(size=(n, 3)), labels,
                             dtype=jnp.float64)
    batches = jax.tree.map(lambda a: jnp.stack([a] * 5), batch)
    problem = OptimizationProblem(objective=obj, optimizer=OptimizerType.LBFGS,
                                  config=cfg)
    return jax.jit(jax.vmap(
        lambda b, w: problem.run(b, w, has_l1=solver == "owlqn"))).lower(
            batches, jnp.zeros((5, 3), jnp.float64)).as_text()


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS), ids="-".join)
def test_the_quasi_newton_programs_are_the_parent_s(case):
    assert hashlib.sha256(_lowered(*case).encode()).hexdigest() \
        == PARENT_PROGRAMS[case]
