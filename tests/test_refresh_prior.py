"""GLMix's daily refresh through ``GameEstimator.fit``: with
``use_warm_start_as_prior`` every random effect is retrained toward the
warm model's means and SIMPLE variances, entity by entity, inside the
projected solves (``EntityPriors``), and exports today's variances with
the prior's precision in them.  Held against the plain float64
reference ``benchmark/reference/refresh.py``; the import join on its own
cases; the programs of a random effect without a prior against the
parent's StableHLO.  Small sizes on the CPU; nothing here is a
performance number."""

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

from benchmark.reference import refresh  # noqa: E402
from photon_ml_tpu import telemetry  # noqa: E402
from photon_ml_tpu.config import training_config_from_json  # noqa: E402
from photon_ml_tpu.data.sparse_rows import SparseRows  # noqa: E402
from photon_ml_tpu.estimators import game_estimator  # noqa: E402
from photon_ml_tpu.estimators.game_estimator import GameEstimator  # noqa: E402
from photon_ml_tpu.game import coordinates  # noqa: E402
from photon_ml_tpu.game.dataset import GameDataset  # noqa: E402
from photon_ml_tpu.io.model_io import load_game_model, save_game_model  # noqa: E402

WIDTH, N, N_VALID, USERS = 40, 1200, 200, 30
GONE, NEW = 10_003, 20_010  # a user of day N alone, a user of day N+1 alone
# Two float32 solves of one entity's problem by different routes end
# this far apart at most (tests/test_re_stream.py, ``F32_SOLVE_ATOL``).
F32_SOLVE_ATOL = 2e-3


def _day(structure, seed=9):
    """(train, valid) of one day: the rows drawn from ``structure``, the
    truth (a dense fixed effect, every (user, column) slope) from
    ``seed``; a sparse per-user shard of ``WIDTH`` columns, the last a
    constant, three entries a row.  User 10's rows belong to ``GONE`` on
    day 0 (a user of day 0 alone) and to ``NEW`` on day 1 (a user of day
    1 alone)."""
    draw = np.random.default_rng(structure)
    n = N + N_VALID
    user = (USERS * draw.random(n) ** 2.5).astype(np.int64) * 7 + 3
    user[user == 10] = GONE if structure == 0 else NEW
    cols = np.concatenate(
        [np.sort(draw.choice(WIDTH - 1, (n, 2)), axis=1),
         np.full((n, 1), WIDTH - 1)], axis=1)
    cols[:, 1] = np.where(cols[:, 1] == cols[:, 0],
                          (cols[:, 0] + 1) % (WIDTH - 1), cols[:, 1])
    cols.sort(axis=1)
    x = draw.normal(size=(n, 5)).astype(np.float32)
    vals = draw.normal(1.0, 0.3, n * 3).astype(np.float32)
    truth = np.random.default_rng(seed)
    slope = truth.normal(0, 1.0, (NEW + 1, WIDTH))
    margins = x @ truth.normal(size=5) + (
        slope[user[:, None], cols] * vals.reshape(n, 3)).sum(axis=1)
    labels = (draw.random(n) < 1 / (1 + np.exp(-margins))).astype(
        np.float32)
    shard = SparseRows(indptr=np.arange(n + 1, dtype=np.int64) * 3,
                       cols=cols.reshape(-1).astype(np.int32), vals=vals)
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
                           "max_iters": 100, "tolerance": 1e-9,
                           "variance_type": "SIMPLE"}}],
        "update_sequence": ["global", "per_user"], "n_iterations": 1,
        "evaluators": ["AUC"], "intercept": True}, **fields)))


def _refresh(model_dir, **fields):
    return _config(warm_start_model_dir=model_dir,
                   locked_coordinates=["global"],
                   use_warm_start_as_prior=True, **fields)


def _table(part):
    """A projected random effect's export as ``refresh`` takes it:
    ``(keys, means, variances)`` sorted by ``entity * WIDTH + column``."""
    entity_at = part.grouping.entity_row_map()
    keys, means, variances = [], [], []
    for b, ids in enumerate(part.projection.feature_ids):
        entity = np.asarray(part.grouping.entity_ids, np.int64)[
            entity_at[b, :len(ids)]]
        slot, local = np.nonzero(ids >= 0)
        keys.append(entity[slot] * WIDTH + ids[slot, local])
        means.append(np.asarray(part.coefficient_blocks[b],
                                np.float64)[slot, local])
        variances.append(np.asarray(part.variance_blocks[b],
                                    np.float64)[slot, local])
    keys = np.concatenate(keys)
    order = np.argsort(keys)
    return (keys[order], np.concatenate(means)[order],
            np.concatenate(variances)[order])


@pytest.fixture(scope="module")
def days(tmp_path_factory):
    """Day N fitted and saved; day N+1's rows and its refresh fit."""
    yesterday, _valid = _day(0)
    model_dir = str(tmp_path_factory.mktemp("yesterday"))
    before = GameEstimator(_config()).fit(yesterday)[0]
    save_game_model(before.model, _config().task_type, model_dir)
    train, valid = _day(1)
    today = GameEstimator(_refresh(model_dir)).fit(train, valid)[0]
    return {"dir": model_dir, "before": before, "train": train,
            "valid": valid, "today": today}


def _plain(days, part):
    """The plain reference's pieces for ``part``, today's per-user
    model: the rows, the offsets its solver saw (the locked global's
    scores) and yesterday's table."""
    train = days["train"]
    shard = train.features["user_shard"]
    rows = refresh.Rows(shard.indptr, shard.cols, shard.vals,
                        train.entity_ids["userId"], WIDTH)
    offsets = np.asarray(days["today"].descent.scores["global"], np.float64)
    return rows, offsets, _table(days["before"].model.models["per_user"])


def test_a_refresh_fit_is_the_plain_solve_toward_yesterday(days):
    """Coefficients, f_e's gradient and SIMPLE variances of every
    (entity, column) of the day against the plain float64 solve, the
    entities and columns that yesterday did not have among them."""
    part = days["today"].model.models["per_user"]
    rows, offsets, yesterday = _plain(days, part)
    keys, means, variances = _table(part)
    assert np.array_equal(keys, rows.pairs)
    _mu, precision = refresh.prior_at(yesterday, rows.pairs)
    owner = rows.pairs // WIDTH
    # a user new today, columns new to a returning user, and priors
    assert (precision[owner == NEW] == 0).all() and (owner == NEW).any()
    returning = (owner != NEW)
    assert (precision[returning] == 0).any()
    assert (precision[returning] > 0).mean() > 0.3
    assert GONE in yesterday[0] // WIDTH and GONE not in owner

    pairs, want = refresh.solve(rows, offsets, days["train"].labels, 1.0,
                                yesterday, 1.0)
    np.testing.assert_allclose(means, want, atol=F32_SOLVE_ATOL)
    prior = refresh.prior_at(yesterday, rows.pairs)
    _value, norm, at_start = refresh.coordinate_end(
        rows, means, offsets, days["train"].labels, 1.0, prior, 1.0)
    assert norm / at_start < 1e-3
    plain = refresh.variances(rows, means, offsets, days["train"].labels,
                              1.0, prior, 1.0)
    np.testing.assert_allclose(variances, plain, rtol=1e-4)
    # the prior's precision is in them: a pair with a prior is tighter
    # than the same pair without one
    without = refresh.variances(rows, means, offsets, days["train"].labels,
                                1.0, (prior[0], 0 * prior[1]), 1.0)
    assert (variances[precision > 0] < 0.9 * without[precision > 0]).all()


def test_the_locked_global_is_yesterdays_bit_for_bit(days):
    before = np.asarray(
        days["before"].model.models["global"].coefficients.means)
    after = np.asarray(
        days["today"].model.models["global"].coefficients.means)
    assert after.dtype == before.dtype == np.float32
    assert np.array_equal(after.view(np.uint32), before.view(np.uint32))


def test_exported_variances_round_trip_for_tomorrow(days, tmp_path):
    save_game_model(days["today"].model, _config().task_type, str(tmp_path))
    loaded, _task = load_game_model(str(tmp_path))
    for got, want in zip(loaded.models["per_user"].variance_blocks,
                         days["today"].model.models["per_user"]
                         .variance_blocks):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prior_weight_zero_is_the_fit_without_a_prior(days):
    train, valid = days["train"], days["valid"]
    zero = GameEstimator(_refresh(days["dir"], prior_weight=0.0)).fit(
        train, valid)[0].model.models["per_user"]
    none = GameEstimator(_config(
        warm_start_model_dir=days["dir"], locked_coordinates=["global"])).fit(
        train, valid)[0].model.models["per_user"]
    for planes in ("coefficient_blocks", "variance_blocks"):
        for a, b in zip(getattr(zero, planes), getattr(none, planes)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


def test_a_chunked_bucket_slices_the_prior_with_its_entities(days,
                                                             monkeypatch):
    """Buckets solved a few entities at a time (``_solve_bucket``'s
    ``lax.map``): each chunk gathers its rows of the prior's blocks,
    and the padding lanes of the last chunk get none."""
    monkeypatch.setattr(coordinates, "_ONE_SHOT_ENTITIES", 2)
    monkeypatch.setattr(coordinates, "_CHUNK_ENTITIES", 3)
    jax.clear_caches()
    try:
        fit = GameEstimator(_refresh(days["dir"])).fit(
            days["train"], days["valid"])[0]
        counts = [coordinates._bucket_chunks(b.shape[0])[0]
                  for b in fit.model.models["per_user"].coefficient_blocks]
        assert max(counts) > 1
        for a, b in zip(fit.model.models["per_user"].coefficient_blocks,
                        days["today"].model.models["per_user"]
                        .coefficient_blocks):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=F32_SOLVE_ATOL)
    finally:
        jax.clear_caches()


def test_the_entity_mesh_carries_the_prior_with_the_blocks(days):
    """Entity-sharded blocks (two devices, each bucket padded to them):
    the prior's blocks are sized by the padded shapes and give the
    one-device fit's coefficients."""
    meshed = GameEstimator(_refresh(days["dir"], n_devices=2)).fit(
        days["train"], days["valid"])[0].model.models["per_user"]
    for got, want in zip(meshed.coefficient_blocks,
                         days["today"].model.models["per_user"]
                         .coefficient_blocks):
        np.testing.assert_allclose(np.asarray(got)[:want.shape[0]],
                                   np.asarray(want), atol=F32_SOLVE_ATOL)


def test_streamed_random_effects_refuse_the_prior(days, tmp_path):
    with pytest.raises(ValueError, match="streamed"):
        GameEstimator(_refresh(days["dir"], re_chunk_entities=8,
                               spill_dir=str(tmp_path))).fit(days["train"])


# -- the import join -------------------------------------------------------------

def _saved(coord, entity_value):
    """A saved model over ``coord``'s grouping and subspaces whose
    coefficient of (entity, column) is ``entity_value(entity, column)``
    and whose variance is 1 + column / 100."""
    from photon_ml_tpu.models.game import RandomEffectModel

    entity_at = coord.grouping.entity_row_map()
    means, variances = [], []
    for b, ids in enumerate(coord.projection.feature_ids):
        entity = np.asarray(coord.grouping.entity_ids)[
            np.maximum(entity_at[b, :len(ids)], 0)][:, None]
        means.append(np.where(ids >= 0, entity_value(entity, ids), 0)
                     .astype(np.float32))
        variances.append(np.where(ids >= 0, 1 + ids / 100, 1)
                         .astype(np.float32))
    return RandomEffectModel(
        coefficient_blocks=[jnp.asarray(m) for m in means],
        grouping=coord.grouping, feature_shard="user_shard",
        variance_blocks=[jnp.asarray(v) for v in variances],
        projection=coord.projection)


def _coordinate(data):
    return coordinates.build_random_effect_coordinate_sparse(
        "userId", data, "user_shard",
        game_estimator.GLMObjective(
            loss=game_estimator.TaskType.LOGISTIC_REGRESSION.loss,
            reg=game_estimator.RegularizationContext.l2(1.0),
            norm=game_estimator.NormalizationContext.identity()),
        global_dim=WIDTH)


def test_the_join_keeps_what_both_days_have_and_nothing_else(monkeypatch):
    """By (entity, global column): a pair both days have keeps
    yesterday's mean and gets precision 1/σ²; a column new to a
    returning user, and every column of a new user, gets mean 0 and
    precision 0; a user of yesterday alone leaves nothing; the stage
    says how many of each."""
    recorded = []
    real = telemetry.stage

    def stage(name, *args, **counts):
        recorded.append(real(name, *args, **counts))
        return recorded[-1]

    monkeypatch.setattr(telemetry, "stage", stage)
    old = _coordinate(_day(0)[0])
    new = _coordinate(_day(1)[0])
    saved = _saved(old, lambda e, c: e * 1000.0 + c)
    estimator = GameEstimator(_config())
    priors, with_prior = estimator._random_prior("per_user", saved, new)

    had = {(int(e), int(c)) for e, c in _pairs(old)}
    entity_at = new.grouping.entity_row_map()
    seen = {"kept": 0, "new_column": 0, "new_user": 0}
    for b, ids in enumerate(new.projection.feature_ids):
        entity = np.asarray(new.grouping.entity_ids)[entity_at[b, :len(ids)]]
        mu, precision = (np.asarray(priors.means[b]),
                         np.asarray(priors.precisions[b]))
        for s, e in enumerate(entity):
            for j, c in enumerate(ids[s]):
                if c < 0:
                    assert precision[s, j] == 0
                elif (e, c) in had:
                    assert mu[s, j] == e * 1000.0 + c
                    assert precision[s, j] == pytest.approx(
                        1 / np.float32(1 + c / 100), rel=1e-6)
                    seen["kept"] += 1
                else:
                    assert mu[s, j] == 0 and precision[s, j] == 0
                    seen["new_user" if e == NEW else "new_column"] += 1
    assert all(seen.values()), seen
    assert GONE not in set(np.asarray(new.grouping.entity_ids).tolist())
    (imported,) = [s.counts for s in recorded
                   if s.name == "re_prior_import"]
    assert {key: imported[key] for key in (
        "coordinate", "entities", "coefficients", "entities_with_prior",
        "coefficients_with_prior")} == {
        "coordinate": "per_user",
        "entities": int(new.grouping.n_total_entities),
        "coefficients": sum(seen.values()),
        "entities_with_prior": with_prior,
        "coefficients_with_prior": seen["kept"]}
    assert 0 < with_prior < new.grouping.n_total_entities
    assert float(priors.weight) == 1.0


def _pairs(coord):
    entity_at = coord.grouping.entity_row_map()
    for b, ids in enumerate(coord.projection.feature_ids):
        entity = np.asarray(coord.grouping.entity_ids)[
            entity_at[b, :len(ids)]]
        slot, local = np.nonzero(ids >= 0)
        yield from zip(entity[slot], ids[slot, local])


def test_a_saved_model_without_variances_gives_no_prior():
    coord = _coordinate(_day(1)[0])
    saved = _saved(coord, lambda e, c: c * 1.0)
    saved.variance_blocks = None
    assert GameEstimator(_config())._random_prior(
        "per_user", saved, coord) == (None, 0)


def test_the_refresh_stages_say_what_was_done(days, monkeypatch):
    recorded = []
    real = telemetry.stage

    def stage(name, *args, **counts):
        recorded.append(real(name, *args, **counts))
        return recorded[-1]

    monkeypatch.setattr(telemetry, "stage", stage)
    GameEstimator(_refresh(days["dir"])).fit(days["train"], days["valid"])
    by_name = {}
    for s in recorded:
        by_name.setdefault(s.name, []).append(s.counts)
    (imported,) = by_name["re_prior_import"]
    (variances,) = by_name["re_variances"]
    part = days["today"].model.models["per_user"]
    real_coefficients = sum(int((f >= 0).sum())
                            for f in part.projection.feature_ids)
    # (a stage that compiled also carries its compile totals)
    assert {key: variances[key] for key in
            ("coordinate", "entities", "coefficients")} == {
        "coordinate": "per_user",
        "entities": part.grouping.n_total_entities,
        "coefficients": real_coefficients}
    assert imported["coefficients"] == real_coefficients
    trains = {c["coordinate"]: c for c in by_name["coord_train"]}
    assert trains["per_user"]["prior_entities"] \
        == imported["entities_with_prior"] > 0
    assert "global" not in trains          # locked: never trained
    assert {"re_prior_import", "re_variances"} <= set(telemetry.STAGES)


def test_a_fit_without_a_prior_runs_neither_stage(days, monkeypatch):
    recorded = []
    real = telemetry.stage

    def stage(name, *args, **counts):
        recorded.append(real(name, *args, **counts))
        return recorded[-1]

    monkeypatch.setattr(telemetry, "stage", stage)
    config = _config()
    config.coordinates[1].optimizer.variance_type = "NONE"
    config.validate()
    GameEstimator(config).fit(days["train"], days["valid"])
    names = [s.name for s in recorded]
    assert "re_prior_import" not in names and "re_variances" not in names
    (user,) = [s.counts for s in recorded if s.name == "coord_train"
               and s.counts["coordinate"] == "per_user"]
    assert "prior_entities" not in user


# -- the programs of a random effect without a prior -------------------------

# sha256 of the StableHLO text each case lowers to, read on the parent
# commit e1dce0e (``git archive`` into a scratch directory) and on this
# tree, at the bucket shapes of glmix-kdd12's and game5-kdd12's random
# effects (entities, capacity, width), 3,185,000 training rows.
BUCKETS = {
    "glmix_kdd12_per_user": [(182_062, 4, 31), (274_510, 16, 103),
                             (18_515, 64, 391), (782, 256, 1_511),
                             (35, 1_024, 5_918), (1, 4_096, 12_996)],
    "glmix_kdd12_per_ad": [(11_119, 256, 25), (2_752, 1_024, 25),
                           (123, 4_096, 25), (6, 16_384, 25)],
    "game5_kdd12_per_user": [(182_062, 4, 2), (274_510, 16, 2),
                             (18_515, 64, 2), (782, 256, 2), (35, 1_024, 2),
                             (1, 4_096, 2)],
    "game5_kdd12_per_item": [(11_119, 256, 1), (2_752, 1_024, 1),
                             (123, 4_096, 1), (6, 16_384, 1)],
}
N_ROWS = 3_185_000
PARENT_PROGRAMS = {
    "glmix_kdd12_per_user.solve":
        "cb8c0b4e045ab46681622e3bedd7482b276551603ca512ab955c36e8f410e849",
    "glmix_kdd12_per_user.donating_solve":
        "28d1c34412e34b38850d349ce433544c73b4e68d42ed8ea71e3ceec1d75d6178",
    "glmix_kdd12_per_user.variances":
        "add90087376157ab694f3eba9f1f0f2358fd62ca3d87b601e6cf61c83ee4bc2f",
    "glmix_kdd12_per_ad.solve":
        "4e637fe5324753678163a2172499a8c2a752eeb54cb1b506e0a4d8b4f9ed09b5",
    "glmix_kdd12_per_ad.donating_solve":
        "89f2406bc7b929756cb4dcf376cdc451d4bc5e048e5ac846c557144081b7bca5",
    "glmix_kdd12_per_ad.variances":
        "a7d297b8a387c5bed0c641d23ef6f0dcf8fcbd08d7f7fbd8d9acc9e78646d142",
    "game5_kdd12_per_user.solve":
        "919e67cc18a558cf29dcb488b8749c22893ab20032930b5619cb6c5cfd44b86b",
    "game5_kdd12_per_user.donating_solve":
        "401fe093224f69d1b3cefb8cff63dfc81cfcd8c052b071dde4bab402b6d9fca4",
    "game5_kdd12_per_user.variances":
        "60a931643cfe624ee995945ca7841fb2c0c437ce087c68efcbde4e00d70d17c4",
    "game5_kdd12_per_item.solve":
        "5b97ce72a3cd2cd65478a0abad80d1973b765c3e4325994dd0c7de11c3fecbf0",
    "game5_kdd12_per_item.donating_solve":
        "f8ae36a9d962ce556c1f81ce7e0adfc3d8079d1a0b8fb9a33c8fd7c4c97b722e",
    "game5_kdd12_per_item.variances":
        "146656c2d57d879e585d1cebed07c1f1cd7c9afbee6c7b45a3bbbfa3a5de38af",
}


def _program(case):
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    def leaf(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    coordinate, kind = case.split(".")
    buckets = BUCKETS[coordinate]
    rows = [min(e * c, N_ROWS) for e, c, _p in buckets]
    blocks = ([leaf((e, c, p)) for e, c, p in buckets],
              *[[leaf((e, c)) for e, c, _p in buckets]] * 3,
              *[[leaf((n,), jnp.int32) for n in rows]] * 3)
    objective = GLMObjective(
        loss=losses.LOGISTIC, reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity())
    w = [leaf((e, p)) for e, _c, p in buckets]
    if kind == "variances":
        return coordinates._re_variances.lower(objective, blocks, w,
                                               leaf((N_ROWS,)))
    solve = (coordinates._re_train_donating if kind == "donating_solve"
             else coordinates._re_train)
    return solve.lower(OptimizerType.LBFGS, OptimizerConfig(max_iters=10),
                       False, objective, blocks, leaf((N_ROWS,)), w)


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_a_random_effect_without_a_prior_lowers_to_the_parent_s_program(
        case):
    text = _program(case).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[case]
