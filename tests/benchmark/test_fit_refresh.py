"""The operation ``fit_refresh`` and its generator at the configuration's
rehearsal size on the CPU: day N+1 is ``glmix-kdd12``'s data, day N
another draw of the same populations; a program whose random effects
take no prior is refused before any data is made; the limits' rules;
each control fails by the condition it is named for; the three
per-layer readers on a recorded list of stages, and on a program
without the stages.  The harness's own parametrised tests
(``test_harness.py``) rehearse the cell whole, damaged and cut short.
Nothing here is a performance number.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import host_spans  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "glmix-kdd12-refresh.fit-cold-refresh"
OPERATION = manifests.load_module(os.path.join(
    REPO, "benchmark", "operations", "fit_refresh.py"))
NEW_METRICS = ("re_prior_import_s", "re_variance_s", "re_prior_share")
# the condition each control must fail (it may fail others too)
CONTROLLED_BY = {"prior_dropped": "optimal_with_prior",
                 "bfloat16_re": "random_effect_exact",
                 "variances_without_prior": "variances_exact",
                 "two_iterations": "optimal_with_prior",
                 "half_rows": "optimal_with_prior"}


def _generator():
    cell = manifests.resolve(MANIFEST, CELL)
    return cell, manifests.load_module(cell["generator_path"])


@pytest.fixture(scope="module")
def fitted():
    cell, generator = _generator()
    config = OPERATION.rehearsal_config(cell["config"])
    data = generator.make(5, **config["generator"]["params"])
    state = OPERATION.prepare(config, cell["traffic"], data)
    return cell, state, OPERATION.one(state)


def test_the_cell_is_this_operations_and_states_its_limits(fitted):
    cell, state, outcome = fitted
    assert cell["traffic"]["operation"] == "fit_refresh"
    assert cell["traffic"]["plan_cache"] is False
    fields = state["training_config"]
    assert fields.locked_coordinates == ["global"]
    assert fields.use_warm_start_as_prior and fields.prior_weight == 1.0
    assert fields.warm_start_model_dir.startswith(OPERATION.MODEL_ROOT)
    assert os.path.isdir(fields.warm_start_model_dir)
    assert OPERATION.limit_problems(cell["config"]) == []
    for key in ("random_effect_rtol", "variance_rtol"):
        assert state["config"][key] == cell["config"][key]
    for broken in (
            dict(cell["config"], variance_rtol=1e-3),
            dict(cell["config"], random_effect_rtol={"scores": 1e-3}),
            dict(cell["config"], gradient_rtol={}),
            dict(cell["config"], gradient_rtol={"per_item": 0.1}),
            dict(cell["config"], gradient_rtol={"per_user": 0.6,
                                                "per_ad": 0.1}),
            dict(cell["config"], auc_gain_floor=-0.01),
            dict(cell["config"], training_config=dict(
                cell["config"]["training_config"], n_iterations=2))):
        assert OPERATION.limit_problems(broken), broken
    for key in OPERATION.LIMIT_KEYS:
        without = {k: v for k, v in cell["config"].items() if k != key}
        assert OPERATION.limit_problems(without), key
    # every random effect exports its variances for the next day
    for name in ("per_user", "per_ad"):
        assert outcome["model"].models[name].variance_blocks is not None


def test_a_random_effect_without_a_limit_is_read_and_held_to_nothing(fitted):
    _cell, state, outcome = fitted
    held = dict(state, config=dict(state["config"],
                                   gradient_rtol={"per_ad": 0.5}))
    check = OPERATION.reference_check(held, outcome)
    assert "gradient.per_user" not in check["compared"]
    assert check["unheld"]["gradient.per_user"] > 0
    assert "gradient.per_ad" in check["compared"] and check["correct"]


@pytest.mark.parametrize("halved", ["per_user", "per_ad"])
def test_day_n_s_exported_variances_are_held_to_the_plain_ones(fitted,
                                                              halved):
    """The prior today's solves and the reference start from is day N's
    export: the sound one is within the limit, either random effect's
    variances halved read one half and fail (e)."""
    _cell, state, outcome = fitted
    whole = OPERATION.reference_check(state, outcome)
    assert whole["compared"]["variance_error.yesterday"]["value"] < \
        whole["compared"]["variance_error.yesterday"]["limit"]
    tables = dict(state["yesterday"]["tables"])
    (keys, means, variances), width = tables[halved]
    tables[halved] = ((keys, means, variances / 2), width)
    halved = dict(state, yesterday=dict(state["yesterday"], tables=tables))
    check = OPERATION.reference_check(halved, outcome)
    assert check["compared"]["variance_error.yesterday"]["value"] == \
        pytest.approx(0.5, rel=1e-3)
    assert not check["conditions"]["variances_exact"]


def test_day_n_plus_one_is_glmix_kdd12_s_data_for_the_same_seed():
    cell, generator = _generator()
    projected = manifests.resolve(MANIFEST, "glmix-kdd12.fit-cold-projected")
    params = dict(cell["config"]["generator"]["params"],
                  **cell["config"]["rehearsal_params"])
    assert params == dict(projected["config"]["generator"]["params"],
                          **projected["config"]["rehearsal_params"])
    ours = generator.make(2**31 + 5, **params)
    theirs = manifests.load_module(projected["generator_path"]).make(
        2**31 + 5, **params)
    for part_a, part_b in zip(ours[:2], theirs[:2]):
        np.testing.assert_array_equal(part_a.labels, part_b.labels)
        for name in part_a.features:
            for leaf in ("indptr", "cols", "vals"):
                np.testing.assert_array_equal(
                    getattr(part_a.features[name], leaf),
                    getattr(part_b.features[name], leaf))
        for key in part_a.entity_ids:
            np.testing.assert_array_equal(part_a.entity_ids[key],
                                          part_b.entity_ids[key])
    for key in ("train_margins", "valid_margins"):
        np.testing.assert_array_equal(ours[2][key], theirs[2][key])
    # day N: another draw of the same populations, the same length
    yesterday, today = ours[2]["yesterday_train"], ours[0]
    assert yesterday.n == today.n
    assert not np.array_equal(yesterday.entity_ids["userId"],
                              today.entity_ids["userId"])
    assert len(np.intersect1d(yesterday.entity_ids["userId"],
                              today.entity_ids["userId"])) > 0
    assert not np.array_equal(yesterday.features["user_shard"].cols,
                              today.features["user_shard"].cols)


def test_a_program_whose_random_effects_take_no_prior_is_refused_first(
        monkeypatch):
    """Before any data is made: the parent of this operation stops at
    once, and says why."""
    from photon_ml_tpu.game import coordinates

    cell, generator = _generator()

    @dataclasses.dataclass
    class WithoutPrior:
        name: str

    def no_data(*_args, **_kwargs):
        raise AssertionError("data was made")

    monkeypatch.setattr(coordinates, "RandomEffectCoordinate", WithoutPrior)
    monkeypatch.setattr(generator, "pattern", no_data)
    params = dict(cell["config"]["generator"]["params"],
                  **cell["config"]["rehearsal_params"])
    with pytest.raises(RuntimeError, match="take no prior"):
        generator.make(3, **params)


@pytest.mark.parametrize("control", sorted(CONTROLLED_BY))
def test_each_control_fails_by_its_condition(fitted, control):
    _cell, state, _outcome = fitted
    with OPERATION.control(control, state) as controlled:
        check = OPERATION.reference_check(controlled,
                                          OPERATION.one(controlled))
    assert not check["correct"]
    assert not check["conditions"][CONTROLLED_BY[control]], check
    assert check["conditions"]["global_locked"]


def test_the_controls_are_the_ones_named():
    assert set(OPERATION.CONTROLS) == set(CONTROLLED_BY)
    with pytest.raises(KeyError, match="no control"):
        with OPERATION.control("nothing", {}):
            pass


# -- the three readers, on a recorded list of stages ---------------------------

def _recorded(stages):
    events = [(start, duration, host_spans.PREFIX + name)
              for name, start, duration, _counts in stages]
    return {"interval": (0, 100_000), "thread": events, "other": [],
            "counts": {event: counts for event, (*_s, counts)
                       in zip(events, stages)}}


STAGES = [
    ("build_coordinates", 1_000, 20_000, {"coordinates": 3}),
    ("re_prior_import", 8_000, 3_000, {
        "coordinate": "per_user", "entities": 40, "coefficients": 300,
        "entities_with_prior": 25, "coefficients_with_prior": 90}),
    ("re_prior_import", 18_000, 1_000, {
        "coordinate": "per_ad", "entities": 10, "coefficients": 100,
        "entities_with_prior": 10, "coefficients_with_prior": 70}),
    ("export_model", 60_000, 9_000, {"coordinates": 3}),
    ("re_variances", 61_000, 5_000, {"coordinate": "per_user",
                                     "entities": 40, "coefficients": 300}),
    ("re_variances", 66_500, 2_000, {"coordinate": "per_ad",
                                     "entities": 10, "coefficients": 100}),
]
EXPECTED = {"re_prior_import_s": 4_000 * 1e-9,
            "re_variance_s": 7_000 * 1e-9,
            "re_prior_share": 100.0 * 160 / 400}


def _reader(name):
    return manifests.load_module(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_a_recorded_stage_list(name, monkeypatch):
    monkeypatch.setattr(host_spans, "stages", lambda ctx: _recorded(STAGES))
    assert _reader(name).read({"trace": {}}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_a_program_without_the_stages(
        name, monkeypatch):
    """The parent's trace has neither stage; and no trace at all."""
    older = [s for s in STAGES if not s[0].startswith("re_")]
    monkeypatch.setattr(host_spans, "stages", lambda ctx: _recorded(older))
    assert _reader(name).read({"trace": {}}) is None
    monkeypatch.undo()
    assert _reader(name).read({}) is None


def test_the_manifest_gives_the_three_to_the_refresh_cell_alone():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "fit_s"
    reported = set(manifests.resolve(MANIFEST, CELL)["layer_metric_paths"])
    assert set(NEW_METRICS) <= reported
    for other in MANIFEST["workloads"]:
        if other["name"] != CELL:
            assert not set(NEW_METRICS) & set(manifests.resolve(
                MANIFEST, other["name"])["layer_metric_paths"])
