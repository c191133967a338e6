"""A dataset's offsets are part of every training margin (ISSUE 37).

``GameDataset.offsets`` (a Poisson model's log exposure, a prior
model's margins) used to be read by validation and scoring and dropped
by training.  Held here: the aggregation identity of a Poisson model,
offsets against a locked coordinate of the same scores, the system
against the plain reference ``benchmark/reference/poisson_enet.py``,
every other training path (each carries them or refuses by name), the
programs of a dataset without offsets, and OWL-QN's own counts.
Nothing timed here is a performance number.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.reference import plain, poisson_enet  # noqa: E402
from photon_ml_tpu.config import (  # noqa: E402
    CoordinateConfig,
    CoordinateKind,
    OptimizerSettings,
    TrainingConfig,
)
from photon_ml_tpu.data.batch import make_dense_batch  # noqa: E402
from photon_ml_tpu.data.normalization import NormalizationContext  # noqa: E402
from photon_ml_tpu.data.sparse_rows import SparseRows  # noqa: E402
from photon_ml_tpu.estimators.game_estimator import GameEstimator  # noqa: E402
from photon_ml_tpu.game import (  # noqa: E402
    FixedEffectCoordinate,
    GameDataset,
    run_coordinate_descent,
)
from photon_ml_tpu.game import coordinates as game_coordinates  # noqa: E402
from photon_ml_tpu.models.glm import TaskType  # noqa: E402
from photon_ml_tpu.ops import losses  # noqa: E402
from photon_ml_tpu.ops.objective import GLMObjective  # noqa: E402
from photon_ml_tpu.ops.regularization import RegularizationContext  # noqa: E402
from photon_ml_tpu.optim import OptimizationProblem, OptimizerConfig  # noqa: E402
from photon_ml_tpu.reliability.checkpoint import RunCheckpointer  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "poisson-enet-kdd12.fit-cold-exposure"


# -- (1) the aggregation identity --------------------------------------------
# k identical rows of offset 0 and counts y_1..y_k have the likelihood of
# one row of label sum(y) and offset log k:
#   sum_i exp(z) - y_i z  =  exp(z + log k) - (sum_i y_i) z.

def _count_groups(rng, groups=90, d=5, users=9):
    x = rng.normal(0, 0.6, (groups, d)).astype(np.float32)
    user = rng.integers(0, users, groups)
    k = rng.integers(1, 6, groups)
    rate = np.exp(x @ rng.normal(0, 0.5, d)
                  + rng.normal(0, 0.5, users)[user] - 0.3)
    rows = np.repeat(np.arange(groups), k)
    counts = rng.poisson(rate[rows]).astype(np.float32)

    def dataset(idx, labels, offsets):
        return GameDataset(
            labels=labels,
            features={"f": x[idx], "u": np.ones((len(idx), 1), np.float32)},
            entity_ids={"userId": user[idx]}, offsets=offsets)

    expanded = dataset(rows, counts, None)
    aggregated = dataset(
        np.arange(groups),
        np.bincount(rows, weights=counts).astype(np.float32),
        np.log(k).astype(np.float32))
    return expanded, aggregated


def _poisson_game(sweeps=6):
    def settings():
        return OptimizerSettings(max_iters=200, reg_weight=1.0,
                                 tolerance=1e-9)

    config = TrainingConfig(
        task_type=TaskType.POISSON_REGRESSION,
        coordinates=[
            CoordinateConfig(name="global", kind=CoordinateKind.FIXED_EFFECT,
                             feature_shard="f", optimizer=settings()),
            CoordinateConfig(name="per_user",
                             kind=CoordinateKind.RANDOM_EFFECT,
                             feature_shard="u", entity_key="userId",
                             optimizer=settings())],
        update_sequence=["global", "per_user"], n_iterations=sweeps,
        # each user's own intercept is the model's: a second one in the
        # fixed effect only slows the descent between the two
        intercept=False, evaluators=[], validate_per_iteration=False)
    config.validate()
    return config


def _coefficients(model):
    """{coordinate: array}, a random effect's by ascending entity id."""
    fixed = np.asarray(model.models["global"].coefficients.means)
    part = model.models["per_user"]
    return {"global": fixed,
            "per_user": np.asarray(part.all_coefficients())[
                np.argsort(part.grouping.entity_ids)]}


@pytest.fixture(scope="module")
def aggregation():
    expanded, aggregated = _count_groups(np.random.default_rng(11))
    stripped = GameDataset(labels=aggregated.labels,
                           features=aggregated.features,
                           entity_ids=aggregated.entity_ids)
    return {name: _coefficients(
        GameEstimator(_poisson_game()).fit(data)[0].model)
        for name, data in (("expanded", expanded),
                           ("aggregated", aggregated),
                           ("stripped", stripped))}


@pytest.mark.parametrize("coordinate", ["global", "per_user"])
def test_k_rows_of_counts_fit_as_one_row_of_their_sum_and_log_k(
        aggregation, coordinate):
    """Coefficient for coefficient, within what two float32 solves of
    one convex objective reach: a float32 objective is flat to about
    the root of its rounding around its minimum, so the two end 5e-5 to
    1.1e-3 apart over three seeds of this data, at 6 sweeps as at 20.
    The same rows with the offsets taken off fit another model
    altogether."""
    expanded = aggregation["expanded"][coordinate]
    np.testing.assert_allclose(aggregation["aggregated"][coordinate],
                               expanded, rtol=0, atol=3e-3)
    assert np.max(np.abs(aggregation["stripped"][coordinate]
                         - expanded)) > 0.05


# -- (2) offsets are a locked coordinate's scores -----------------------------

def test_offsets_train_as_a_locked_coordinate_of_the_same_scores():
    rng = np.random.default_rng(5)
    n = 400
    x = rng.normal(size=(n, 5)).astype(np.float32)
    prior = rng.normal(0, 1.5, n).astype(np.float32)
    labels = (rng.uniform(size=n) < 1 / (1 + np.exp(-(x[:, 0] + prior)))
              ).astype(np.float32)

    def coordinate(name, features):
        return FixedEffectCoordinate(
            name=name, batch=make_dense_batch(features, labels),
            problem=OptimizationProblem(
                objective=GLMObjective(
                    loss=losses.LOGISTIC, reg=RegularizationContext.l2(0.5),
                    norm=NormalizationContext.identity()),
                config=OptimizerConfig(max_iters=40)))

    with_offsets = run_coordinate_descent(
        {"a": coordinate("a", x)}, ["a"], 2, offsets=jnp.asarray(prior))
    with_locked = run_coordinate_descent(
        {"a": coordinate("a", x), "prior": coordinate("prior",
                                                      prior[:, None])},
        ["a"], 2, locked_coordinates={"prior": jnp.ones(1, jnp.float32)})
    np.testing.assert_allclose(with_offsets.coefficients["a"],
                               with_locked.coefficients["a"],
                               rtol=0, atol=1e-6)
    # the total says what it includes: the offsets and every score
    np.testing.assert_allclose(
        with_offsets.total_scores,
        prior + np.asarray(with_offsets.scores["a"]), rtol=0, atol=1e-6)
    without = run_coordinate_descent({"a": coordinate("a", x)}, ["a"], 2)
    assert np.max(np.abs(np.asarray(without.coefficients["a"])
                         - np.asarray(with_offsets.coefficients["a"]))) > 0.05


# -- (3) the system against the plain reference --------------------------------

@pytest.fixture(scope="module")
def rehearsed():
    """The new cell's fit at its configuration's ``rehearsal_params``:
    (state, FitResult, the fixed effect's block as the reference takes
    it, the log exposure of the training rows)."""
    cell = manifests.resolve(MANIFEST, CELL)
    operation = manifests.load_module(cell["operation_path"])
    config = operation.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        7, **config["generator"]["params"])
    state = operation.prepare(config, cell["traffic"], data)
    result = GameEstimator(state["training_config"]).fit(
        state["train"], state["valid"])[0]
    rows = state["train"].features["global"]
    block = (rows.indptr, rows.cols, rows.vals,
             np.asarray(result.model.models["global"].coefficients.means,
                        np.float64), 1.0)
    return state, result, block, np.log(state["truth"]["train_exposure"])


def test_the_fit_is_the_plain_reference_s(rehearsed):
    """The fixed effect is trained first, against the exposure alone.
    Scores: float32 sums of up to 12 products against float64 (read
    4e-7).  Objective and KKT residual: the solver's last record against
    the reference at the exported coefficients, the residual as a share
    of the residual at zero (read 2e-7 and 1e-9).  All three are under
    2^-11 by a wide margin, so a bfloat16 contraction (3.9e-3 on the
    scores) fails the first; dropped offsets move the residual by a
    tenth of itself at zero and a dropped L1 term by 4e-3, and fail the
    third."""
    state, result, block, exposure = rehearsed
    labels = state["train"].labels
    scores = plain.margins(block[:4], [])
    held = np.asarray(result.descent.scores["global"], np.float64)
    assert np.max(np.abs(held - scores) / np.maximum(1, np.abs(scores))) \
        < 4e-6
    end = poisson_enet.fixed_effect_end(block, scores, exposure, labels,
                                        alpha=0.5)
    last = result.descent.history[-1]["global"]
    assert abs(last["value"] - end["value"]) < 1e-5 * abs(end["value"])
    assert abs(last["grad_norm"] - end["kkt_norm"]) \
        < 6e-6 * end["kkt_norm_at_zero"]
    assert end["kkt_norm"] < 0.05 * end["kkt_norm_at_zero"]
    # the residual with the exposure left out is no optimum's
    dropped = poisson_enet.fixed_effect_end(
        block, scores, np.zeros_like(exposure), labels, alpha=0.5)
    assert dropped["kkt_norm"] > 10 * end["kkt_norm"]


def test_the_export_holds_exact_zeros_and_the_program_counts_them(rehearsed):
    state, result, block, exposure = rehearsed
    w = block[3]
    exported = int(np.count_nonzero(w))
    assert 0 < exported < 0.05 * len(w)
    assert result.descent.history[-1]["global"][
        "nonzero_coefficients"] == exported
    # a column no training row has is an exact zero
    seen = np.zeros(len(w), bool)
    seen[state["train"].features["global"].cols] = True
    seen[-1] = True
    assert not np.any(w[~seen])
    # of the coordinates whose gradient lies inside the L1 term's
    # subdifferential, all but the few on their way back are exact zeros
    end = poisson_enet.fixed_effect_end(
        block, plain.margins(block[:4], []), exposure,
        state["train"].labels, alpha=0.5)
    assert np.count_nonzero(w[end["inside"]]) < 0.02 * end["inside"].sum()


def test_validation_and_training_see_the_same_margins(rehearsed):
    """The reported validation loss is the plain loss with the
    validation rows' exposures in."""
    state, result, _block, _exposure = rehearsed
    from photon_ml_tpu.evaluation import EvaluatorType

    cell = manifests.resolve(MANIFEST, CELL)
    operation = manifests.load_module(cell["operation_path"])
    scores = operation.fit._scores(operation.fit._blocks(
        result.model, state, state["valid"]))
    want = poisson_enet.mean_poisson_loss(
        np.log(state["truth"]["valid_exposure"]) + sum(scores.values()),
        state["valid"].labels)
    got = float(result.evaluations[EvaluatorType.POISSON_LOSS])
    assert abs(got - want) < 1e-5 * abs(want)


# -- (4) every other training path carries them or refuses by name -------------

def _logistic_with_offsets(rng, n=384, d=40, k=4):
    cols = np.stack([np.sort(rng.choice(d, k, replace=False))
                     for _ in range(n)]).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    users = rng.integers(0, 12, n)
    prior = rng.normal(0, 1.5, n).astype(np.float32)
    margin = (np.einsum("nk,nk->n", vals, rng.normal(0, 1, d)[cols])
              + rng.normal(0, 0.7, 12)[users] + prior)
    labels = (margin + rng.normal(0, 0.3, n) > 0).astype(np.float32)
    return GameDataset(
        labels=labels,
        features={"f": SparseRows(
            indptr=np.arange(n + 1, dtype=np.int64) * k,
            cols=cols.reshape(-1), vals=vals.reshape(-1)),
            "re": rng.normal(0, 1, (n, 2)).astype(np.float32)},
        entity_ids={"u": users}, offsets=prior, feature_dims={"f": d})


def _path_config(**kw):
    def settings(weight):
        return OptimizerSettings(max_iters=60, reg_weight=weight,
                                 tolerance=1e-7)

    kw.setdefault("sparse_layout", "ELL")
    config = TrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[
            CoordinateConfig(name="global", kind=CoordinateKind.FIXED_EFFECT,
                             feature_shard="f", optimizer=settings(1.0)),
            CoordinateConfig(name="per_u", kind=CoordinateKind.RANDOM_EFFECT,
                             feature_shard="re", entity_key="u",
                             optimizer=settings(2.0))],
        update_sequence=["global", "per_u"], n_iterations=3,
        intercept=False, evaluators=[], validate_per_iteration=False, **kw)
    config.validate()
    return config


def _fixed(result):
    return np.asarray(result.model.models["global"].coefficients.means)


@pytest.fixture(scope="module")
def resident():
    data = _logistic_with_offsets(np.random.default_rng(21))
    stripped = GameDataset(labels=data.labels, features=data.features,
                           entity_ids=data.entity_ids,
                           feature_dims=data.feature_dims)
    return (data, _fixed(GameEstimator(_path_config()).fit(data)[0]),
            _fixed(GameEstimator(_path_config()).fit(stripped)[0]))


def _fit_chunked(data, tmp_path):
    return GameEstimator(_path_config(
        chunk_rows=96, chunk_layout="ELL", sparse_layout="AUTO")).fit(data)[0]


def _fit_mesh(data, tmp_path):
    return GameEstimator(_path_config(n_devices=8)).fit(data)[0]


def _fit_resumed(data, tmp_path):
    """A checkpointed fit, then the same fit resumed from its last
    sweep: the running total comes back offsets and all."""
    first = _path_config(checkpoint_dir=str(tmp_path / "ck"))
    first.n_iterations = 2
    GameEstimator(first).fit(data)
    return GameEstimator(_path_config(checkpoint_dir=str(tmp_path / "ck"),
                                      resume=True)).fit(data)[0]


def _fit_fused(data, tmp_path):
    return GameEstimator(_path_config(
        chunk_rows=96, chunk_layout="ELL", sparse_layout="AUTO",
        cd_fused=True)).fit(data)[0]


@pytest.mark.parametrize("path,atol,refusal", [
    (_fit_chunked, 5e-3, None),
    (_fit_mesh, 3e-3, None),
    (_fit_resumed, 1e-3, None),
    (_fit_fused, None, "cd_fused does not carry a dataset's offsets"),
], ids=["chunked", "mesh", "checkpoint_resume", "fused_sweep"])
def test_a_training_path_carries_the_offsets_or_refuses_by_name(
        resident, tmp_path, path, atol, refusal):
    data, with_offsets, without = resident
    if refusal:
        with pytest.raises(ValueError, match=refusal):
            path(data, tmp_path)
        return
    got = _fixed(path(data, tmp_path))
    np.testing.assert_allclose(got, with_offsets, rtol=0, atol=atol)
    # and nowhere near the fit that never saw them
    assert np.max(np.abs(without - with_offsets)) > 20 * atol


def test_the_fused_loop_itself_refuses_offsets():
    with pytest.raises(ValueError, match="does not carry a dataset's offsets"):
        run_coordinate_descent({}, [], 1, fused_engine=object(),
                               offsets=jnp.zeros(3))


def test_a_swept_grid_trains_every_lane_against_the_offsets(resident):
    data, _with, _without = resident

    def config(**kw):
        settings = OptimizerSettings(max_iters=60, reg_weight=1.0,
                                     tolerance=1e-7)
        cfg = TrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinates=[CoordinateConfig(
                name="global", kind=CoordinateKind.FIXED_EFFECT,
                feature_shard="f", optimizer=settings)],
            update_sequence=["global"], n_iterations=1, intercept=False,
            evaluators=[], validate_per_iteration=False,
            sparse_layout="ELL", **kw)
        cfg.validate()
        return cfg

    lanes = GameEstimator(config(
        reg_weight_grid={"global": [0.5, 4.0]})).fit(data)
    for lane, weight in zip(lanes, (0.5, 4.0)):
        point = config()
        point.coordinates[0].optimizer.reg_weight = weight
        np.testing.assert_allclose(
            _fixed(lane), _fixed(GameEstimator(point).fit(data)[0]),
            rtol=0, atol=2e-3)


def test_cd_mid_sweep_resume_restores_the_total_with_its_offsets(tmp_path):
    """Killed in sweep 2's second coordinate and resumed: the restored
    running total holds the offsets, so the resumed run is the
    uninterrupted one."""
    def coordinates():
        rng = np.random.default_rng(5)
        labels = (rng.uniform(size=300) < 0.5).astype(np.float32)

        def coordinate(name, width):
            return FixedEffectCoordinate(
                name=name, batch=make_dense_batch(
                    rng.normal(size=(300, width)).astype(np.float32), labels),
                problem=OptimizationProblem(
                    objective=GLMObjective(
                        loss=losses.LOGISTIC,
                        reg=RegularizationContext.l2(0.5),
                        norm=NormalizationContext.identity()),
                    config=OptimizerConfig(max_iters=30)))

        return {"a": coordinate("a", 5), "b": coordinate("b", 3)}

    class Interrupt(Exception):
        pass

    class Failing:
        def __init__(self, inner):
            self.inner, self.calls = inner, 0

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def train(self, *a, **kw):
            self.calls += 1
            if self.calls == 2:
                raise Interrupt()
            return self.inner.train(*a, **kw)

    offsets = jnp.asarray(np.random.default_rng(6).normal(0, 1.5, 300),
                          jnp.float32)
    reference = run_coordinate_descent(coordinates(), ["a", "b"], 3,
                                       offsets=offsets)
    failing = coordinates()
    failing["b"] = Failing(failing["b"])
    with pytest.raises(Interrupt):
        run_coordinate_descent(
            failing, ["a", "b"], 3, offsets=offsets,
            checkpointer=RunCheckpointer(str(tmp_path), every_solver_iters=1))
    resumed = run_coordinate_descent(
        coordinates(), ["a", "b"], 3, offsets=offsets, resume=True,
        checkpointer=RunCheckpointer(str(tmp_path), every_solver_iters=1,
                                     resume=True))
    for name in ("a", "b"):
        np.testing.assert_allclose(resumed.coefficients[name],
                                   reference.coefficients[name],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(resumed.total_scores, reference.total_scores,
                               rtol=1e-5, atol=1e-5)


# -- (5) a dataset without offsets runs the parent's programs ------------------

# sha256 of the StableHLO text each solve of the three older cells lowers
# to at its configuration's rehearsal size (``jit(...).lower(...)
# .as_text()`` under this file's settings), read on the parent commit
# d819ca5 and on this tree with ``_solve_texts`` below: the same on both.
# A change of JAX moves them all at once; a change of one solver's
# arithmetic moves the cells that run it.  PR 38 read them again on its
# parent 02da805 and on its tree: L-BFGS along the margins lowers to the
# same text with ``_line_search`` carrying what a trial keeps (nothing,
# in that mode).
PARENT_PROGRAMS = json.loads(open(os.path.join(
    REPO, "tests", "resources", "solve_programs_d819ca5.json")).read())


def _solve_texts(cell_name, offsets=False):
    """{coordinate: StableHLO text} of the cell's solves at rehearsal
    size, for a dataset without offsets (or, asked so, with them: a
    solve is handed one vector either way)."""
    cell = manifests.resolve(MANIFEST, cell_name)
    operation = manifests.load_module(cell["operation_path"])
    config = operation.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        3, **config["generator"]["params"])
    state = operation.prepare(config, cell["traffic"], data)
    estimator = GameEstimator(state["training_config"])
    train = state["train"]
    assert (train.offsets is not None) == offsets
    coordinates = estimator._build_coordinates(
        train, estimator._prepare(train), {})
    seen = jnp.zeros((train.n,), jnp.float32)
    texts = {}
    for name, c in coordinates.items():
        head = (c.problem.optimizer, c.problem.config, c.problem.has_l1(),
                c.problem.objective)
        if isinstance(c, FixedEffectCoordinate):
            lowered = game_coordinates._fixed_train_local_donating.lower(
                *head, c.batch, seen, c.train_idx, c.train_weights,
                c.initial_coefficients())
        else:
            lowered = game_coordinates._re_train_donating.lower(
                *head, c._blocks(), seen, c.initial_coefficients())
        texts[name] = lowered.as_text()
    return texts


@pytest.mark.parametrize("cell_name", sorted(PARENT_PROGRAMS))
def test_without_offsets_a_cell_s_solves_lower_to_the_parent_s_programs(
        cell_name):
    found = {name: hashlib.sha256(text.encode()).hexdigest()
             for name, text in _solve_texts(cell_name).items()}
    assert found == PARENT_PROGRAMS[cell_name]


# The same of the exposure cell's three solves, read on the parent commit
# 02da805 by PR 38, which changed what OWL-QN keeps of a trial: its two
# random effects are L2 and walk the margins, the parent's programs; its
# fixed effect is the L1 solve, whose program is the one that moved.
EXPOSURE_PARENT_PROGRAMS = {
    "global": "dba3ca6e65dfae37f036a19de1baa9c104d63319a9bb959c8384299ceaa27bbf",
    "per_item": "f04174fc736b0fbb3e02f89f1f680e74c4708c5509afbf33a2e8dea6e4ad3e05",
    "per_user": "d4799ecfd557561dcdb483b2be443941b6f2993ae0a991db9e579eaadd251d08",
}


def test_of_the_exposure_cell_s_solves_only_the_l1_one_left_the_parent_s():
    found = {name: hashlib.sha256(text.encode()).hexdigest()
             for name, text in _solve_texts(CELL, offsets=True).items()}
    assert sorted(name for name in found
                  if found[name] != EXPOSURE_PARENT_PROGRAMS[name]) \
        == ["global"]


def test_without_offsets_the_total_starts_at_the_scores_alone():
    """No zeros are added for a dataset that brought no offsets: the
    first coordinate is handed the very zeros the loop always made."""
    seen = []

    class Recording(FixedEffectCoordinate):
        def train(self, offsets, warm_start=None, **kw):
            seen.append(offsets)
            return super().train(offsets, warm_start, **kw)

    rng = np.random.default_rng(2)
    x = rng.normal(size=(50, 3)).astype(np.float32)
    coordinate = Recording(
        name="a", batch=make_dense_batch(
            x, (rng.uniform(size=50) < 0.5).astype(np.float32)),
        problem=OptimizationProblem(
            objective=GLMObjective(loss=losses.LOGISTIC,
                                   reg=RegularizationContext.l2(0.5),
                                   norm=NormalizationContext.identity()),
            config=OptimizerConfig(max_iters=5)))
    result = run_coordinate_descent({"a": coordinate}, ["a"], 1)
    assert not np.any(np.asarray(seen[0]))
    np.testing.assert_array_equal(result.total_scores, result.scores["a"])


# -- (6) OWL-QN counts what it pays --------------------------------------------

@pytest.mark.parametrize("track_states", [True, False])
def test_owlqn_counts_its_start_every_trial_and_every_accepted_point(
        track_states):
    rng = np.random.default_rng(9)
    x = rng.normal(size=(200, 12)) * rng.uniform(0.5, 5.0, 12)
    y = rng.poisson(np.exp(np.clip(x @ rng.normal(0, 0.2, 12), -3, 2)))
    calls = []

    def counting(w):
        jax.debug.callback(lambda: calls.append(1))
        return objective.value_and_gradient(w, batch)

    from photon_ml_tpu.optim.lbfgs import lbfgs_solve

    batch = make_dense_batch(x, y.astype(float))
    objective = GLMObjective(loss=losses.POISSON,
                             reg=RegularizationContext.l2(0.1),
                             norm=NormalizationContext.identity())
    result = jax.jit(lambda w: lbfgs_solve(
        counting, w, OptimizerConfig(max_iters=12, tolerance=0.0,
                                     track_states=track_states),
        l1_weight=0.4))(jnp.zeros(12, jnp.float32))
    jax.effects_barrier()
    iterations, trials = int(result.iterations), int(result.ls_trials)
    assert iterations == 12 and trials > iterations
    assert int(result.forward_passes) == 1 + trials + iterations \
        == len(calls)
    if track_states:
        assert trials == int(np.nansum(np.asarray(result.tracker.ls_trials)))


def test_the_stages_of_an_exposure_fit_say_so(rehearsed, spans_of):
    """``cd_initial_scores`` carries ``offsets`` where the dataset
    brought them, ``coord_train`` of the L1 coordinate its counts and
    ``nonzero_coefficients``, ``export_model`` the same count."""
    state, result, block, _exposure = rehearsed
    _fit, spans = spans_of(
        GameEstimator(state["training_config"]).fit, state["train"])
    by_name = {}
    for span in spans:
        by_name.setdefault(span["name"], []).append(span.get("args") or {})
    assert by_name["cd_initial_scores"][0]["offsets"] == 1
    fixed = [args for args in by_name["coord_train"]
             if args["coordinate"] == "global"][0]
    assert fixed["nonzero_coefficients"] == np.count_nonzero(block[3])
    # a contraction at the start and one a trial the orthant projection
    # clipped; the trials it clipped nothing of walk the margins, after
    # one X·d in their search (the first, from w = 0, walks them all)
    walked = fixed["walked_trials"]
    xd_searches = fixed["forward_passes"] - (1 + fixed["ls_trials"] - walked)
    assert fixed["ls_trials"] > fixed["solver_iterations"] > 0
    assert 1 <= xd_searches <= min(walked, fixed["solver_iterations"])
    assert all("nonzero_coefficients" not in args
               for args in by_name["coord_train"]
               if args["coordinate"] != "global")
    assert by_name["export_model"][0]["nonzero_coefficients"] \
        == fixed["nonzero_coefficients"]
    # and a dataset without offsets says nothing of them
    bare = GameDataset(labels=state["train"].labels,
                       features=state["train"].features,
                       entity_ids=state["train"].entity_ids,
                       feature_dims=state["train"].feature_dims)
    _fit, spans = spans_of(
        GameEstimator(state["training_config"]).fit, bare)
    initial = [s for s in spans if s["name"] == "cd_initial_scores"][0]
    assert "offsets" not in (initial.get("args") or {})
