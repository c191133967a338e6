"""A random effect over a sparse shard, solved in per-entity subspaces
(``build_random_effect_coordinate_sparse``), through
``GameEstimator.fit`` (ISSUE 35): against the same fit with the shard
densified and unprojected; what the ``re_project`` stage and a
random effect's ``coord_train`` say of it; and ``FitResult.descent``.
Small sizes on the CPU; nothing here is a performance number."""

import json

import numpy as np
import pytest

from photon_ml_tpu import native, telemetry
from photon_ml_tpu.config import training_config_from_json
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.estimators import game_estimator
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.estimators.game_transformer import GameTransformer
from photon_ml_tpu.evaluation import EvaluatorType
from photon_ml_tpu.game import coordinates
from photon_ml_tpu.game.dataset import GameDataset, group_by_entity
from photon_ml_tpu.game.projector import build_subspace_projection

WIDTH, N, N_VALID, USERS = 60, 1500, 300, 40
# Two float32 solves of one entity's problem in different shapes (its
# subspace's width against the shard's) end this far apart at most:
# ``F32_SOLVE_ATOL`` of tests/test_re_stream.py has the derivation.
F32_SOLVE_ATOL = 2e-3


def _data(seed=4):
    """(train, valid) with a dense fixed-effect shard and a sparse
    per-user shard of ``WIDTH`` columns (the last a constant), three
    entries a row; users of 1 to a few hundred rows."""
    rng = np.random.default_rng(seed)
    n = N + N_VALID
    user = (USERS * rng.random(n) ** 2.5).astype(np.int64) * 7 + 3
    cols = np.concatenate(
        [np.sort(rng.choice(WIDTH - 1, (n, 2)), axis=1),
         np.full((n, 1), WIDTH - 1)], axis=1)
    cols[:, 1] = np.where(cols[:, 1] == cols[:, 0],
                          (cols[:, 0] + 1) % (WIDTH - 1), cols[:, 1])
    cols.sort(axis=1)
    shard = SparseRows(indptr=np.arange(n + 1, dtype=np.int64) * 3,
                       cols=cols.reshape(-1).astype(np.int32),
                       vals=rng.normal(1.0, 0.3, n * 3).astype(np.float32))
    x = rng.normal(size=(n, 5)).astype(np.float32)
    slope = rng.normal(0, 1.0, (USERS * 7 + 4, WIDTH))
    margins = x @ rng.normal(size=5) + (
        slope[user[:, None], cols] * shard.vals.reshape(n, 3)).sum(axis=1)
    labels = (rng.random(n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    data = GameDataset(labels=labels,
                       features={"global": x, "user_shard": shard},
                       entity_ids={"userId": user},
                       feature_dims={"user_shard": WIDTH})
    return data.take(np.arange(N)), data.take(np.arange(N, n))


def _config(**fields):
    return training_config_from_json(json.dumps(dict({
        "task_type": "LOGISTIC_REGRESSION",
        "coordinates": [
            {"name": "global", "kind": "FIXED_EFFECT",
             "feature_shard": "global",
             "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                           "max_iters": 30}},
            {"name": "per_user", "kind": "RANDOM_EFFECT",
             "feature_shard": "user_shard", "entity_key": "userId",
             "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                           "max_iters": 40, "tolerance": 1e-7}}],
        "update_sequence": ["global", "per_user"], "n_iterations": 2,
        "evaluators": ["AUC"], "intercept": True}, **fields)))


def _densified(data):
    shard = data.features["user_shard"]
    return GameDataset(
        labels=data.labels,
        features={"global": data.features["global"],
                  "user_shard": shard.to_dense(WIDTH)},
        entity_ids=data.entity_ids)


@pytest.fixture(scope="module")
def fits():
    train, valid = _data()
    projected = GameEstimator(_config()).fit(train, valid)[0]
    dense = GameEstimator(_config()).fit(_densified(train),
                                         _densified(valid))[0]
    return train, valid, projected, dense


def test_projected_coefficients_are_the_dense_fits_on_each_subspace(fits):
    """Every entity's coefficients: the unprojected solve's on the
    columns the entity saw, and no coefficient at all outside them
    (where the unprojected solve, started at zero with a zero gradient,
    never moves)."""
    train, _valid, projected, dense = fits
    part = projected.model.models["per_user"]
    assert part.projection is not None
    assert dense.model.models["per_user"].projection is None
    whole = np.asarray(dense.model.models["per_user"].all_coefficients())
    entity_ids = dense.model.models["per_user"].grouping.entity_ids
    shard = train.features["user_shard"]
    row_user = np.repeat(train.entity_ids["userId"], 3)
    widths = set()
    for i, entity in enumerate(entity_ids):
        saw = np.unique(shard.cols[row_user == entity])
        ours = part.global_coefficients_for(entity)
        np.testing.assert_allclose(ours[saw], whole[i][saw],
                                   atol=F32_SOLVE_ATOL)
        outside = np.setdiff1d(np.arange(WIDTH), saw)
        assert not ours[outside].any() and not whole[i][outside].any()
        b, s = part.grouping.entity_index()[int(entity)]
        ids = part.projection.feature_ids[b][s]
        assert sorted(ids[ids >= 0]) == saw.tolist()
        widths.add(len(saw))
    assert len(widths) > 5 and max(widths) > 3 * min(widths)
    assert np.abs(whole).max() > 0.3


def test_projected_validation_scores_are_the_dense_fits(fits):
    _train, valid, projected, dense = fits
    ours = GameTransformer(model=projected.model,
                           task=GameEstimator(_config()).task).transform(
        valid)
    theirs = GameTransformer(model=dense.model,
                             task=GameEstimator(_config()).task).transform(
        _densified(valid))
    # three entries of about 1.0 a row: three times the coefficients' slack
    np.testing.assert_allclose(ours, theirs, atol=3 * F32_SOLVE_ATOL)
    assert projected.evaluations[EvaluatorType.AUC] == pytest.approx(
        dense.evaluations[EvaluatorType.AUC], abs=2e-3)
    assert projected.evaluations[EvaluatorType.AUC] > 0.6


def test_fit_result_carries_the_descent_it_ran(monkeypatch):
    """``FitResult.descent`` is the very object that
    ``run_coordinate_descent`` returned to the estimator."""
    returned = []
    run = game_estimator.run_coordinate_descent

    def recording(**kwargs):
        returned.append(run(**kwargs))
        return returned[-1]

    monkeypatch.setattr(game_estimator, "run_coordinate_descent", recording)
    train, valid = _data()
    result = GameEstimator(_config(n_iterations=1)).fit(train, valid)[0]
    assert len(returned) == 1 and result.descent is returned[0]
    assert set(result.descent.scores) == {"global", "per_user"}
    assert result.descent.scores["per_user"].shape == (N,)
    assert result.validation_history is result.descent.validation_history


# -- what the stages say --------------------------------------------------------

def _direct_counts(user, shard, base=4):
    """The ``re_project`` counts by a count of their own: per entity
    the rows and the distinct columns, per bucket (capacities the
    powers of ``base`` from 4) the entities and the widest subspace."""
    row_user = np.repeat(user, np.diff(shard.indptr))
    entities = np.unique(user)
    rows = np.array([(user == e).sum() for e in entities])
    width = np.array([len(np.unique(shard.cols[row_user == e]))
                      for e in entities])
    capacity = np.array([4 * base ** int(np.ceil(np.log(max(r, 4) / 4)
                                                 / np.log(base) - 1e-9))
                         for r in rows])
    return {
        "entities": len(entities),
        "buckets": len(set(capacity)),
        "subspace_columns": int(width.sum()),
        "design_elements": int((rows * width).sum()),
        "block_elements": int(sum(
            (capacity == c).sum() * c * width[capacity == c].max()
            for c in set(capacity))),
        "widest": int(width.max()),
    }


def test_projection_counts_are_a_direct_count():
    train, _valid = _data()
    user, shard = train.entity_ids["userId"], train.features["user_shard"]
    grouping = group_by_entity(user, bucket_base=4)
    projection, x_blocks = build_subspace_projection(grouping, shard, WIDTH)
    direct = _direct_counts(user, shard)
    built_by = {"native": int(native.lib() is not None), "workers": 1}
    assert projection.counts(grouping) == dict(direct, **built_by)
    assert direct["block_elements"] == sum(b.size for b in x_blocks)
    assert direct["design_elements"] < direct["block_elements"]
    assert direct["buckets"] >= 3 and direct["widest"] > 20
    # a hand-made grouping: three entities of 2, 2 and 5 rows, subspaces
    # of 2, 3 and 4 columns; capacities 4 and 16
    user = np.array([5, 5, 9, 9, 2, 2, 2, 2, 2])
    rows = [([0, 1], [1, 1]), ([1], [1]), ([3, 4], [1, 1]), ([4, 7], [1, 1]),
            ([0], [1]), ([2], [1]), ([4], [1]), ([6], [1]), ([0, 6], [1, 1])]
    grouping = group_by_entity(user, bucket_base=4)
    projection, x_blocks = build_subspace_projection(grouping, rows, 8)
    assert projection.counts(grouping) == {
        "entities": 3, "buckets": 2, "subspace_columns": 2 + 3 + 4,
        "design_elements": 2 * 2 + 2 * 3 + 5 * 4,
        "block_elements": 2 * 4 * 3 + 1 * 16 * 4, "widest": 4, **built_by}
    assert [b.shape for b in x_blocks] == [(2, 4, 3), (1, 16, 4)]


@pytest.fixture(params=["native", "numpy"])
def library(request, monkeypatch):
    """Both builders of a projection: the native library's (where there
    is one) and, with the library away, the numpy body."""
    if request.param == "numpy":
        monkeypatch.setattr(native, "lib", lambda: None)
    return request.param


@pytest.mark.parametrize("example_entity_kept", [True, False])
def test_projection_is_each_entitys_own_layout(example_entity_kept, library):
    """Entity by entity, in plain loops: an entity's local columns are
    its distinct global columns in ascending order, and each of its
    rows' entries sits at (its slot, the row's place in the entity,
    the column's local index); everything else is padding.  A grouping
    without ``example_entity`` (one read back from a saved model) is
    projected from its (bucket, slot) maps.  By either builder."""
    import dataclasses

    train, _valid = _data()
    user, shard = train.entity_ids["userId"], train.features["user_shard"]
    grouping = group_by_entity(user, bucket_base=4)
    want_ids = [np.full((ne, 1), -1, np.int64) for ne in grouping.n_entities]
    want_x = {}
    for e, uid in enumerate(grouping.entity_ids):
        b, slot = grouping.entity_bucket[e], grouping.entity_slot[e]
        mine = np.flatnonzero(user == uid)
        seen = sorted({int(c) for i in mine for c in shard[i][0]})
        if len(seen) > want_ids[b].shape[1]:
            want_ids[b] = np.pad(
                want_ids[b], ((0, 0), (0, len(seen) - want_ids[b].shape[1])),
                constant_values=-1)
        want_ids[b][slot, :len(seen)] = seen
        for i in mine:
            for c, v in zip(*shard[i]):
                want_x[b, slot, grouping.example_col[i], seen.index(c)] = v
    if not example_entity_kept:
        grouping = dataclasses.replace(grouping, example_entity=None)
    projection, x_blocks = build_subspace_projection(grouping, shard, WIDTH)
    for b, (fids, block) in enumerate(zip(projection.feature_ids, x_blocks)):
        assert fids.dtype == np.int32 and block.dtype == np.float32
        np.testing.assert_array_equal(fids, want_ids[b])
        want = np.zeros(block.shape, np.float32)
        for (b_, slot, pos, loc), v in want_x.items():
            if b_ == b:
                want[slot, pos, loc] = v
        np.testing.assert_array_equal(block, want)


class _Recorded:
    """Stands in for ``telemetry.stage``: keeps every stage's name and
    counts, in the order they closed."""

    def __init__(self):
        self.stages = []

    def __call__(self, name, cat="stage", **counts):
        recorder = self

        class Stage:
            duration_s = 0.0

            def __enter__(self):
                return self

            def set(self, **more):
                counts.update(more)

            def __exit__(self, *exc):
                recorder.stages.append((name, counts))
                return False
        return Stage()


@pytest.mark.parametrize("library", ["native", "numpy"])
def test_the_stages_of_a_projected_fit_say_what_was_built(
        monkeypatch, sha256_of, library):
    """With the library away the fit runs the numpy body, says so
    (``native`` 0) and places the same blocks."""
    train, valid = _data()
    shard = train.features["user_shard"]
    _projection, blocks = build_subspace_projection(
        group_by_entity(train.entity_ids["userId"], bucket_base=4),
        shard, WIDTH)           # by the library, where there is one
    if library == "numpy":
        monkeypatch.setattr(native, "lib", lambda: None)
    recorded = _Recorded()
    monkeypatch.setattr(telemetry, "stage", recorded)
    placed_blocks = []
    place = coordinates._place_re

    def placing(name, x_blocks, *rest):
        placed_blocks.append(sha256_of(x_blocks))
        return place(name, x_blocks, *rest)

    monkeypatch.setattr(coordinates, "_place_re", placing)
    GameEstimator(_config(n_iterations=1)).fit(train, valid)
    assert placed_blocks == [sha256_of(blocks)]
    names = [name for name, _counts in recorded.stages]
    assert "re_project" in telemetry.STAGES
    # inside group_entities: it closes first
    assert names.index("re_project") + 1 == names.index("group_entities")
    (project,) = [c for name, c in recorded.stages if name == "re_project"]
    direct = _direct_counts(train.entity_ids["userId"], shard)
    by_library = library == "native" and native.lib() is not None
    assert project == dict(direct, entity_key="userId", nnz=shard.nnz,
                           bytes=4 * direct["block_elements"],
                           native=int(by_library), workers=1)
    (placed,) = [c for name, c in recorded.stages if name == "place_re"]
    assert placed["bytes"] > project["bytes"]
    trains = {c["coordinate"]: c for name, c in recorded.stages
              if name == "coord_train"}
    assert trains["per_user"]["buckets"] == direct["buckets"]
    assert trains["per_user"]["chunks"] == direct["buckets"]
    assert "buckets" not in trains["global"]


def test_a_bucket_over_the_one_shot_bound_counts_its_chunks(monkeypatch):
    assert coordinates._bucket_chunks(131_072) == (1, 131_072)
    assert coordinates._bucket_chunks(274_510) == (17, 16_148)
    monkeypatch.setattr(coordinates, "_ONE_SHOT_ENTITIES", 4)
    monkeypatch.setattr(coordinates, "_CHUNK_ENTITIES", 3)
    assert coordinates._bucket_chunks(4) == (1, 4)
    assert coordinates._bucket_chunks(10) == (4, 3)
