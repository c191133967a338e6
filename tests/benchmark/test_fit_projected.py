"""The operation ``fit_projected`` (ISSUE 35) at its configuration's
rehearsal size on the CPU: the sixth condition, ``random_effect_exact``;
each control fails where it must; a program whose ``FitResult`` hands no
descent over is refused before any data is made; the five per-layer
readers of the projected random effects on a recorded list of stages;
``limits.py`` at the rehearsal size.  The harness's own parametrised
tests (``test_harness.py``) rehearse the cell whole, damaged and cut
short.  Nothing here is a performance number.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import limits  # noqa: E402
from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "glmix-kdd12.fit-cold-projected"
OPERATION = manifests.load_module(os.path.join(
    REPO, "benchmark", "operations", "fit_projected.py"))
CONDITION = OPERATION.CONDITION
NEW_METRICS = ("re_projection_s", "re_grouping_s", "re_train_s",
               "re_padded_share", "re_block_gb")


def _others_hold(check):
    return all(value for name, value in check["conditions"].items()
               if name != CONDITION)


@pytest.fixture(scope="module")
def fitted():
    cell = manifests.resolve(MANIFEST, CELL)
    config = OPERATION.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    state = OPERATION.prepare(config, cell["traffic"], data)
    return cell, state, OPERATION.one(state)


def test_the_cell_is_this_operations_and_states_its_limit(fitted):
    cell, state, _outcome = fitted
    assert cell["traffic"]["operation"] == "fit_projected"
    assert cell["traffic"]["plan_cache"] is False
    assert state["training_config"].plan_cache_dir is None
    assert OPERATION.LIMIT_KEYS[-1] == "random_effect_rtol"
    assert set(cell["config"]["random_effect_rtol"]) == {"scores"}
    # the rehearsal keeps the cell's own limits of precision
    for key in ("fixed_effect_rtol", "random_effect_rtol"):
        assert state["config"][key] == cell["config"][key]
    wide = dict(cell["config"], random_effect_rtol={"scores": 1e-3})
    assert any("bfloat16" in p for p in OPERATION.limit_problems(wide))
    # the fixed effect's gradient limit has this operation's own cap,
    # under the 1 of a solve returned at its start; the random
    # effects' keep ``fit``'s
    limits = cell["config"]["gradient_rtol"]
    assert OPERATION.limit_problems(cell["config"]) == []
    assert OPERATION.fit.GRADIENT_RTOL_MOST < limits["global"] < 0.95
    for name, value in (("global", 1.0), ("per_user", 0.6)):
        off = dict(cell["config"],
                   gradient_rtol=dict(limits, **{name: value}))
        assert any("gradient_rtol" in p
                   for p in OPERATION.limit_problems(off)), name
    more = dict(cell["config"], random_effect_rtol={"scores": 1e-6,
                                                    "value": 1e-6})
    assert any("nothing else" in p for p in OPERATION.limit_problems(more))


def test_every_shard_is_sparse_and_the_random_effects_are_projected(fitted):
    _cell, state, outcome = fitted
    from photon_ml_tpu.data.sparse_rows import SparseRows

    train = state["train"]
    assert all(isinstance(f, SparseRows) for f in train.features.values())
    assert set(train.entity_ids) == {"userId", "adId"}
    for name in ("per_user", "per_ad"):
        part = outcome["model"].models[name]
        assert part.projection is not None
        # the shard's last column is the constant one: in every
        # entity's subspace, 1.0 in every row
        shard = train.features[part.feature_shard]
        last = shard.cols.reshape(train.n, -1)[:, -1]
        assert (last == part.projection.global_dim - 1).all()
    widths = {ids.shape[1] for ids in
              outcome["model"].models["per_user"].projection.feature_ids}
    assert len(widths) >= 3


def test_a_float32_fit_reads_far_under_the_random_effect_limit(fitted):
    _cell, state, outcome = fitted
    check = OPERATION.reference_check(state, outcome)
    assert check["correct"], check
    assert abs(check["plain_auc"] - outcome["auc"]) < 1e-5
    entry = check["compared"]["random_effect.scores"]
    assert entry == {"value": check["random_effect_rel"]["scores"],
                     "limit": state["config"]["random_effect_rtol"]["scores"]}
    assert 0 < entry["value"] < entry["limit"] / 4
    assert set(outcome["descent"]["scores"]) == {"global", "per_user",
                                                 "per_ad"}
    assert set(outcome["descent"]["last"]) == {"global", "per_user", "per_ad"}
    # every coordinate's gradient is compared, the random effects' too
    assert {"gradient.per_user", "gradient.per_ad",
            "fixed_effect.scores"} <= set(check["compared"])


def test_a_wrong_local_to_global_map_fails_the_random_effect_condition(
        fitted):
    """Two local columns of every per-user subspace swapped on export:
    the model no longer scores the rows as the fit's own blocks did."""
    _cell, state, outcome = fitted
    model = dataclasses.replace(outcome["model"],
                                models=dict(outcome["model"].models))
    part = model.models["per_user"]
    swapped = []
    for ids in part.projection.feature_ids:
        ids = ids.copy()
        ids[:, [0, 1]] = ids[:, [1, 0]]
        swapped.append(ids)
    model.models["per_user"] = dataclasses.replace(
        part, projection=dataclasses.replace(part.projection,
                                             feature_ids=swapped))
    check = OPERATION.reference_check(state, dict(outcome, model=model))
    assert not check["conditions"][CONDITION], check
    entry = check["compared"]["random_effect.scores"]
    assert entry["value"] > 100 * entry["limit"]


def test_bfloat16_in_the_random_effects_fails_by_their_condition(fitted):
    """Every random-effect contraction's result rounded to bfloat16:
    the fixed effect's stay float32, so its condition holds, and the
    random effects' scores are off by 2**-9 of themselves."""
    _cell, state, _outcome = fitted
    with OPERATION.control("bfloat16_re", state) as patched:
        check = OPERATION.reference_check(patched, OPERATION.one(patched))
    assert not check["correct"]
    assert not check["conditions"][CONDITION], check
    assert check["conditions"]["fixed_effect_exact"], check
    entry = check["compared"]["random_effect.scores"]
    assert entry["value"] > 100 * entry["limit"]
    # the patch is gone: a fit after it is sound again
    again = OPERATION.reference_check(state, OPERATION.one(state))
    assert again["correct"], again


def test_slopes_zeroed_is_not_the_fits_model(fitted):
    _cell, state, outcome = fitted
    with OPERATION.control("slopes_zeroed", state) as spoiled:
        bad = OPERATION.one(spoiled)
    assert bad["auc"] == pytest.approx(outcome["auc"], abs=1e-6)
    for name in ("per_user", "per_ad"):
        part = bad["model"].models[name]
        kept = sum(int(np.count_nonzero(np.asarray(b)))
                   for b in part.coefficient_blocks)
        assert 0 < kept <= part.n_entities
    check = OPERATION.reference_check(spoiled, bad)
    assert not check["correct"]
    assert not check["conditions"]["auc_agrees"], check
    assert not check["conditions"][CONDITION], check
    assert check["conditions"]["fixed_effect_exact"], check
    # what it costs in AUC is what the subspaces' slopes were worth
    assert check["plain_auc"] < outcome["auc"] - 0.01


def test_a_program_whose_fit_hands_nothing_over_is_refused(monkeypatch):
    """The parent commit's ``FitResult`` has no ``descent``: the
    generator asks the operation before it makes any data, and
    ``prepare`` asks again."""
    from photon_ml_tpu.estimators import game_estimator

    @dataclasses.dataclass
    class Older:
        model: object
        evaluations: dict
        reg_weights: dict
        validation_history: list = dataclasses.field(default_factory=list)

    monkeypatch.setattr(game_estimator, "FitResult", Older)
    cell = manifests.resolve(MANIFEST, CELL)
    config = OPERATION.rehearsal_config(cell["config"])
    generator = manifests.load_module(cell["generator_path"])
    drawn = []
    monkeypatch.setattr(generator.np.random, "default_rng",
                        lambda *a: drawn.append(a) or 1 / 0)
    with pytest.raises(RuntimeError, match="no field `descent`"):
        generator.make(5, **config["generator"]["params"])
    assert not drawn
    with pytest.raises(RuntimeError, match="no field `descent`"):
        OPERATION.prepare(config, cell["traffic"], None)


def test_no_control_of_another_name():
    with pytest.raises(KeyError, match="no control"):
        with OPERATION.control("nothing", {}):
            pass
    assert OPERATION.CONTROLS == ("bfloat16_re", "two_iterations",
                                  "slopes_zeroed")


def test_limits_py_reads_the_cell_at_its_rehearsal_size():
    """A sound seed with its damaged results, then ``two_iterations``:
    every record says the device and that it is a rehearsal."""
    cell = manifests.resolve(MANIFEST, CELL)
    records = list(limits.readings(cell, [7], ["two_iterations"], [7],
                                   rehearsal=True, damaged=True))
    assert [r["control"] for r in records] == [
        None, "damaged: per_ad zeroed", "damaged: slopes zeroed",
        "two_iterations"]
    assert records[0]["correct"] is True
    assert not any(r["correct"] for r in records[1:3])
    for record in records:
        json.dumps(record)
        assert (record["platform"], record["rehearsal"]) == ("cpu", True)
        assert "random_effect.scores" in record["compared"]
    # two iterations leave the random effects' gradients far from the
    # whole solve's
    whole, short = records[0]["compared"], records[3]["compared"]
    assert short["gradient.per_user"]["value"] \
        > 3 * whole["gradient.per_user"]["value"]


# -- the five readers, on a recorded list of stages ----------------------------

def _recorded(stages):
    """``host_spans.stages``' result for a list of (name, start,
    duration, counts) on the traced thread."""
    events = [(start, duration, host_spans.PREFIX + name)
              for name, start, duration, _counts in stages]
    return {"interval": (0, 100_000), "thread": events, "other": [],
            "counts": {event: counts for event, (*_s, counts)
                       in zip(events, stages)}}


STAGES = [
    ("group_entities", 1_000, 9_000, {"entity_key": "userId"}),
    ("re_project", 2_000, 6_000, {
        "entity_key": "userId", "design_elements": 30,
        "block_elements": 120, "bytes": 480}),
    ("place_re", 10_000, 500, {"entity_key": "userId", "bytes": 1_500}),
    ("group_entities", 11_000, 4_000, {"entity_key": "adId"}),
    ("re_project", 11_500, 1_000, {
        "entity_key": "adId", "design_elements": 20, "block_elements": 80,
        "bytes": 320}),
    ("place_re", 15_000, 500, {"entity_key": "adId", "bytes": 500}),
    ("coord_train", 20_000, 30_000, {"coordinate": "global",
                                     "solver_iterations": 30}),
    ("coord_train", 51_000, 7_000, {"coordinate": "per_user", "buckets": 6,
                                    "chunks": 33}),
    ("coord_train", 59_000, 2_000, {"coordinate": "per_ad", "buckets": 4,
                                    "chunks": 4}),
]
EXPECTED = {
    "re_projection_s": (6_000 + 1_000) * 1e-9,
    "re_grouping_s": (9_000 + 4_000) * 1e-9,
    "re_train_s": (7_000 + 2_000) * 1e-9,
    "re_padded_share": 100.0 * (1 - 50 / 200),
    "re_block_gb": 2_000 / 1e9,
}


def _reader(name):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_a_recorded_stage_list(name, monkeypatch):
    monkeypatch.setattr(host_spans, "stages",
                        lambda ctx: _recorded(STAGES))
    assert _reader(name).read({"trace": {}}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_a_program_without_the_stages(name,
                                                               monkeypatch):
    """The parent's trace: no ``re_project``, no ``buckets`` on a
    ``coord_train``, no random effect at all; and no trace."""
    older = [(stage, start, duration,
              {k: v for k, v in counts.items()
               if k not in ("buckets", "chunks")})
             for stage, start, duration, counts in STAGES
             if stage == "coord_train"]
    monkeypatch.setattr(host_spans, "stages", lambda ctx: _recorded(older))
    assert _reader(name).read({"trace": {}}) is None
    monkeypatch.undo()
    assert _reader(name).read({}) is None


def test_the_manifest_gives_the_five_to_the_projected_cell_alone():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "fit_s"
    reported = set(manifests.resolve(MANIFEST, CELL)["layer_metric_paths"])
    assert set(NEW_METRICS) <= reported
    # the key-less metrics that move fit_s fall to it by metrics_of's rule
    assert {"device_idle_share", "peak_hbm_gb", "grr_kernel_ms",
            "compile_s.window", "plan_build_s", "placement_s", "cd_host_s",
            "validation_s", "host_unnamed_s"} <= reported
    assert not reported & {"entity_grouping_s", "device_busy_ms",
                           "fe_forward_passes", "fe_tail_ms"}
    for other in ("game5-kdd.fit-cold", "game5-kdd12.fit-cold"):
        assert not set(NEW_METRICS) & set(
            manifests.resolve(MANIFEST, other)["layer_metric_paths"])
