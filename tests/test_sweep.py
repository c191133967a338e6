"""Batched λ-sweep ≡ sequential per-point fits (ISSUE 2 tentpole).

The swept surfaces (``ops.objective`` lane sweep, ``optim.lbfgs
.lbfgs_solve_swept``, ``optim.streaming.streaming_lbfgs_solve_swept``,
the coordinate ``train_swept`` entries, and the GameEstimator grid /
tuned wiring) must reproduce the sequential one-λ-at-a-time fits to
float-reorder tolerance on BOTH the resident and chunked paths —
including an L1 (OWL-QN) lane — while paying a fraction of the data
passes (asserted through the chunk-sweep odometer).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.config import (
    CoordinateConfig,
    CoordinateKind,
    OptimizerSettings,
    TrainingConfig,
    TuningConfig,
)
from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.data.chunked_batch import build_chunked_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.evaluators import EvaluatorType
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.models.glm import TaskType
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import (
    RegularizationContext,
    RegularizationType,
    SweptRegularization,
)
from photon_ml_tpu.optim import (
    ChunkedGLMObjective,
    OptimizerConfig,
    lbfgs_solve,
    lbfgs_solve_swept,
    streaming_lbfgs_solve,
    streaming_lbfgs_solve_swept,
)

# Weakest lane kept ≥ 0.1: below that the logistic objective is flat
# enough that f32 solves stall-terminate at slightly different points
# (values equal to 1e-5, one-coordinate wander) — real float
# indeterminacy, not a sweep defect.
LAMS = [10.0, 1.0, 0.1]


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _sparse_problem(rng, n=1500, d=300, k=6):
    cols = np.stack([
        np.sort(rng.choice(d, k, replace=False)) for _ in range(n)
    ]).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    w_true = rng.normal(0, 0.8, d) * (rng.uniform(size=d) < 0.3)
    m = np.einsum("nk,nk->n", vals, w_true[cols])
    labels = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(
        np.float32)
    rows = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    return rows, labels


def _objective(lam=1.0):
    return GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(lam),
        norm=NormalizationContext.identity(),
    )


# -- optimizer-level equivalence -------------------------------------------


@pytest.mark.parametrize("use_map", [False, True])
def test_lbfgs_solve_swept_matches_sequential(rng, use_map):
    """Each swept lane's solution ≡ the per-λ lbfgs_solve (vmap lane
    axis AND the lax.map lane-loop fallback for unbatchable kernels)."""
    rows, labels = _sparse_problem(rng)
    d = 300
    batch = make_sparse_batch(rows, d, labels)
    obj = _objective()
    cfg = OptimizerConfig(max_iters=200, tolerance=1e-7)

    def vg(w, l2):
        o = obj.replace(reg=obj.reg.replace(l2_weight=l2))
        return o.value_and_gradient(w, batch)

    W0 = jnp.zeros((len(LAMS), d), jnp.float32)
    res = lbfgs_solve_swept(vg, W0, jnp.asarray(LAMS, jnp.float32), cfg,
                            use_map=use_map)
    for i, lam in enumerate(LAMS):
        o = _objective(lam)
        r = lbfgs_solve(lambda w: o.value_and_gradient(w, batch),
                        jnp.zeros((d,), jnp.float32), cfg)
        np.testing.assert_allclose(np.asarray(res.w[i]), np.asarray(r.w),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(float(res.value[i]), float(r.value),
                                   rtol=1e-5)
        assert bool(res.converged[i])


def test_owlqn_swept_matches_sequential(rng):
    """Elastic-net lanes: swept OWL-QN ≡ per-λ OWL-QN, with the lane
    sparsity pattern tracking λ."""
    rows, labels = _sparse_problem(rng)
    d = 300
    batch = make_sparse_batch(rows, d, labels)
    obj = _objective()
    cfg = OptimizerConfig(max_iters=80, tolerance=1e-7)
    lams = [1.0, 0.3, 0.03]
    reg = SweptRegularization.from_grid(
        RegularizationType.ELASTIC_NET, lams, elastic_net_alpha=0.5)
    assert reg.has_l1()

    def vg(w, l2):
        o = obj.replace(reg=obj.reg.replace(l2_weight=l2))
        return o.value_and_gradient(w, batch)

    W0 = jnp.zeros((len(lams), d), jnp.float32)
    res = lbfgs_solve_swept(vg, W0, reg.l2_weights, cfg,
                            l1_weights=reg.l1_vectors(d, None))
    zeros = []
    for i, lam in enumerate(lams):
        o = GLMObjective(
            loss=losses.LOGISTIC,
            reg=RegularizationContext.elastic_net(lam, 0.5),
            norm=NormalizationContext.identity(),
        )
        l1 = jnp.broadcast_to(o.reg.l1_weight, (d,))
        r = lbfgs_solve(lambda w: o.value_and_gradient(w, batch),
                        jnp.zeros((d,), jnp.float32), cfg, l1_weight=l1)
        np.testing.assert_allclose(np.asarray(res.w[i]), np.asarray(r.w),
                                   rtol=5e-3, atol=5e-3)
        zeros.append(int(np.sum(np.asarray(res.w[i]) == 0.0)))
    # Orthant-wise L1 must actually sparsify, more at larger λ.
    assert zeros[0] > zeros[-1]
    assert zeros[0] > 20


@pytest.mark.parametrize("layout", ["ell", "grr"])
def test_streaming_swept_matches_sequential_and_amortizes(rng, layout):
    """Chunked path: every batched lane ≡ its sequential streaming fit,
    and the batched grid pays well under half the data passes (the
    chunk-sweep odometer — passes per solver iteration L → ~1).  The
    GRR layout exercises the lane-loop (lax.map) per-chunk program."""
    rows, labels = _sparse_problem(rng)
    d = 300
    cb = build_chunked_batch(rows, d, labels, n_chunks=3, layout=layout)
    cfg = OptimizerConfig(max_iters=60, tolerance=1e-6)
    lams = [10.0, 3.0, 1.0, 0.3, 0.1]
    reg = SweptRegularization.from_grid(RegularizationType.L2, lams)
    cobj = ChunkedGLMObjective(_objective(), cb, max_resident=3)
    W0 = jnp.zeros((len(lams), d), jnp.float32)
    res = streaming_lbfgs_solve_swept(
        lambda W: cobj.value_and_gradient_swept(W, reg),
        lambda W: cobj.value_swept(W, reg),
        W0, cfg)
    batched_passes = cobj.sweeps

    seq_passes = 0
    for i, lam in enumerate(lams):
        co = ChunkedGLMObjective(_objective(lam), cb, max_resident=3)
        r = streaming_lbfgs_solve(co.value_and_gradient,
                                  jnp.zeros((d,), jnp.float32), cfg,
                                  value_fn=co.value)
        seq_passes += co.sweeps
        np.testing.assert_allclose(np.asarray(res.w[i]), np.asarray(r.w),
                                   rtol=5e-3, atol=5e-3)
    # ELL lanes mostly accept α=1 → ~0.3× the sequential passes; GRR's
    # reordered contractions backtrack more (each extra trial is one
    # shared value sweep), landing ~0.5× at L=5 — both well below L×,
    # and the ratio improves with lane count.
    bound = 0.5 if layout == "ell" else 0.6
    assert batched_passes <= seq_passes * bound, (
        f"batched {batched_passes} passes vs sequential {seq_passes}")


# -- estimator-level equivalence -------------------------------------------


def _glm_dataset(rng, n=1200, d=200, k=5, sparse=False):
    if sparse:
        rows, labels = _sparse_problem(rng, n=n, d=d, k=k)
        return GameDataset(labels=labels, features={"g": rows},
                           entity_ids={}, feature_dims={"g": d})
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    m = x @ (rng.normal(0, 1, d) * (rng.uniform(size=d) < 0.4))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    return GameDataset(labels=y, features={"g": x}, entity_ids={})


def _glm_split(rng, n=1600, d=60):
    """One generative model, split train/validation (a held-out set
    from a DIFFERENT model would make AUC meaningless)."""
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    m = x @ (rng.normal(0, 1, d) * (rng.uniform(size=d) < 0.4))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    cut = int(0.8 * n)
    return (GameDataset(labels=y[:cut], features={"g": x[:cut]},
                        entity_ids={}),
            GameDataset(labels=y[cut:], features={"g": x[cut:]},
                        entity_ids={}))


def _glm_config(**over):
    base = dict(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[CoordinateConfig(
            name="fixed", kind=CoordinateKind.FIXED_EFFECT,
            feature_shard="g",
            optimizer=OptimizerSettings(max_iters=200, tolerance=1e-7),
        )],
        update_sequence=["fixed"],
        evaluators=[EvaluatorType.AUC],
    )
    base.update(over)
    return TrainingConfig(**base)


def _assert_grid_matches_sequential(cfg, train, valid, grid,
                                    tol=2e-3):
    est = GameEstimator(cfg)
    results = est.fit(train, valid)
    assert len(results) == len(grid)
    est_seq = GameEstimator(cfg)
    prep = est_seq._prepare(train)
    for r, lam in zip(results, grid):
        assert r.reg_weights["fixed"] == lam
        seq = est_seq._fit_point(train, prep, {"fixed": lam}, valid,
                                 None)
        np.testing.assert_allclose(
            np.asarray(r.model.models["fixed"].coefficients.means),
            np.asarray(seq.model.models["fixed"].coefficients.means),
            rtol=tol, atol=tol)
        if valid is not None:
            assert (abs(r.evaluations[EvaluatorType.AUC]
                        - seq.evaluations[EvaluatorType.AUC]) < 5e-3)
    return results


def test_estimator_grid_swept_resident(rng, monkeypatch):
    """Eligible fixed-effect grids take the swept path (never
    _fit_point) and match sequential fits lane by lane — the resident
    batch, intercept reg-mask exercised."""
    train, valid = _glm_split(rng)
    grid = [0.1, 1.0, 10.0]
    cfg = _glm_config(reg_weight_grid={"fixed": grid}, intercept=True)

    calls = []
    orig = GameEstimator._fit_point

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(GameEstimator, "_fit_point", spy)
    est = GameEstimator(cfg)
    results = est.fit(train, valid)
    assert calls == [], "eligible grid fell back to per-point fits"
    monkeypatch.undo()

    est_seq = GameEstimator(cfg)
    prep = est_seq._prepare(train)
    for r, lam in zip(results, grid):
        seq = est_seq._fit_point(train, prep, {"fixed": lam}, valid,
                                 None)
        np.testing.assert_allclose(
            np.asarray(r.model.models["fixed"].coefficients.means),
            np.asarray(seq.model.models["fixed"].coefficients.means),
            rtol=2e-3, atol=2e-3)
        # Per-iteration validation survives the swept path: one entry
        # per CD sweep, last entry == final evaluations (the
        # _fit_point contract).
        assert len(r.validation_history) == cfg.n_iterations
        assert r.validation_history[-1] == r.evaluations


def test_estimator_grid_swept_chunked(rng):
    """Chunked (streaming) estimator path: swept grid ≡ sequential
    per-point chunked fits."""
    train = _glm_dataset(rng, sparse=True)
    grid = [5.0, 1.0, 0.2]
    cfg = _glm_config(reg_weight_grid={"fixed": grid}, intercept=False,
                      chunk_rows=400, chunk_layout="ELL",
                      chunk_max_resident=8)
    # The swept and the per-point streamed solves sum the chunks' float32
    # partials in different orders, and each stops where float32 can no
    # longer resolve a decrease of f: ½·λ·δ² ≲ eps·|f| of slack, so
    # δ ≲ sqrt(2·2⁻²⁴·|f|/λ) ≈ 2e-2 on the weakest lane (|f| ≈ 700 over
    # 1200 rows, λ = 0.2; a column has ~30 nonzeros, so the data adds
    # little curvature).  All but a coefficient or two agree to 5e-3.
    _assert_grid_matches_sequential(cfg, train, None, grid, tol=3e-2)


def test_estimator_grid_swept_owlqn_lane(rng):
    """An elastic-net (OWL-QN) grid sweeps batched and matches the
    sequential fits — the L1 lane acceptance case."""
    train, valid = _glm_split(rng)
    grid = [8.0, 0.5]
    cfg = _glm_config(reg_weight_grid={"fixed": grid})
    cfg.coordinates[0].optimizer.regularization = (
        RegularizationType.ELASTIC_NET)
    cfg.coordinates[0].optimizer.elastic_net_alpha = 0.5
    results = _assert_grid_matches_sequential(cfg, train, valid, grid,
                                              tol=5e-3)
    w_strong = np.asarray(
        results[0].model.models["fixed"].coefficients.means)
    # OWL-QN at the strong-λ lane must sparsify (intercept excluded).
    assert int(np.sum(w_strong[:-1] == 0.0)) > 5


def test_estimator_grid_multi_coordinate_stays_sequential(rng,
                                                          monkeypatch):
    """A grid over a config with a random effect is NOT swept-eligible
    and keeps the per-point path."""
    from photon_ml_tpu.utils.synthetic import make_movielens_like

    data = make_movielens_like(n_users=40, n_items=1, n_obs=800, seed=3)
    train = GameDataset(
        labels=data["labels"],
        features={"g": data["x"],
                  "u": np.ones((len(data["labels"]), 1), np.float32)},
        entity_ids={"per_user": data["user_ids"]},
    )
    cfg = _glm_config(
        coordinates=[
            CoordinateConfig(
                name="fixed", kind=CoordinateKind.FIXED_EFFECT,
                feature_shard="g",
                optimizer=OptimizerSettings(max_iters=30)),
            CoordinateConfig(
                name="user", kind=CoordinateKind.RANDOM_EFFECT,
                feature_shard="u", entity_key="per_user",
                optimizer=OptimizerSettings(max_iters=20)),
        ],
        update_sequence=["fixed", "user"],
        reg_weight_grid={"fixed": [0.1, 1.0]},
        evaluators=[],
    )
    calls = []
    orig = GameEstimator._fit_point

    def spy(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    monkeypatch.setattr(GameEstimator, "_fit_point", spy)
    results = GameEstimator(cfg).fit(train)
    assert len(results) == 2
    assert len(calls) == 2


def test_estimator_grid_swept_mesh(rng):
    """Mesh-sharded fixed effect: the swept grid lane-loops around the
    shard_mapped objective (8 virtual devices via conftest) and matches
    the sequential distributed fits."""
    train = _glm_dataset(rng, n=800, d=40)
    grid = [5.0, 0.5]
    cfg = _glm_config(reg_weight_grid={"fixed": grid}, n_devices=8,
                      intercept=False)
    cfg.coordinates[0].optimizer.max_iters = 60
    _assert_grid_matches_sequential(cfg, train, None, grid, tol=5e-3)


# -- batched tuning ---------------------------------------------------------


def test_fit_tuned_batched_trials(rng, monkeypatch):
    """Swept-eligible tuning evaluates whole proposal batches (no
    per-point _fit_point) and returns n_trials results, both modes."""
    train, valid = _glm_split(rng)
    monkeypatch.setattr(
        GameEstimator, "_fit_point",
        lambda self, *a, **kw: pytest.fail("tuned fell back"))
    for mode, n_trials in (("RANDOM", 5), ("BAYESIAN", 6)):
        cfg = _glm_config(tuning=TuningConfig(
            n_trials=n_trials, mode=mode, trial_batch=3,
            reg_weight_ranges={"fixed": {"low": 0.01, "high": 10.0}}))
        trials = GameEstimator(cfg).fit_tuned(train, valid)
        assert len(trials) == n_trials
        for t in trials:
            assert 0.01 <= t.reg_weights["fixed"] <= 10.0
            assert 0.5 <= t.evaluations[EvaluatorType.AUC] <= 1.0


def test_propose_batch_spreads(rng):
    """GP propose_batch: one fit, q distinct spread proposals; random
    propose_batch: q draws."""
    from photon_ml_tpu.hyperparameter import (
        GaussianProcessSearch,
        ParamRange,
        RandomSearch,
        SearchSpace,
    )

    space = SearchSpace([ParamRange("lam", 1e-3, 10.0)])
    rs = RandomSearch(space, seed=0)
    batch = rs.propose_batch([], 4)
    assert len(batch) == 4
    assert len({round(b["lam"], 9) for b in batch}) == 4

    gp = GaussianProcessSearch(space, seed=0, min_observations=3)
    history = [({"lam": lam}, -abs(np.log10(lam)))
               for lam in (0.01, 0.1, 1.0, 5.0)]
    batch = gp.propose_batch(history, 4)
    assert len(batch) == 4
    units = [space.to_unit(b)[0] for b in batch]
    # Spread: no two picks within the min-distance radius.
    for i in range(4):
        for j in range(i + 1, 4):
            assert abs(units[i] - units[j]) >= 0.05 - 1e-6


def test_tuner_run_batched_contract():
    """run_batched: respects n_trials across uneven batches and feeds
    whole config lists to the evaluator."""
    from photon_ml_tpu.hyperparameter import (
        HyperparameterTuner,
        ParamRange,
        SearchSpace,
        TunerMode,
    )

    space = SearchSpace([ParamRange("lam", 0.01, 10.0)])
    tuner = HyperparameterTuner(space, mode=TunerMode.RANDOM, seed=0)
    seen_batches = []

    def evaluate_batch(configs):
        seen_batches.append(len(configs))
        return [(float(c["lam"]), {"lam": c["lam"]}) for c in configs]

    trials = tuner.run_batched(evaluate_batch, 7, batch_size=3)
    assert len(trials) == 7
    assert seen_batches == [3, 3, 1]
    best = tuner.best(trials)
    assert best.metric == max(t.metric for t in trials)
