"""The ``fit`` operation's fixed-effect condition (ISSUE 34) and its
controls, at the rehearsal size on the CPU: what the fit computed
through its own plans (training scores, the solver's last value and
gradient norm) against the plain float64 reference; the fit with every
fixed-effect contraction rounded to bfloat16, which must fail by that
condition alone; and a whole run of ``run.py`` past its look for a
chip, which must say ``correct: false`` when the timed path is broken
underneath.  Nothing here is a performance number.
"""

import copy
import dataclasses
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import limits  # noqa: E402
from benchmark import run as run_py  # noqa: E402
from benchmark.harness import manifest as manifests  # noqa: E402
from benchmark.reference import plain  # noqa: E402

MANIFEST = manifests.load_manifest()
FIT = manifests.load_module(os.path.join(REPO, "benchmark", "operations",
                                         "fit.py"))
FIT_CELLS = [w["name"] for w in MANIFEST["workloads"]
             if manifests.resolve(MANIFEST, w["name"])["traffic"][
                 "operation"] == "fit"]
CONDITION = "fixed_effect_exact"


def _others_hold(check):
    return all(value for name, value in check["conditions"].items()
               if name != CONDITION)


@pytest.fixture(scope="module", params=FIT_CELLS)
def fitted(request):
    cell = manifests.resolve(MANIFEST, request.param)
    config = FIT.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    state = FIT.prepare(config, cell["traffic"], data)
    return cell, state, FIT.one(state)


def test_a_float32_fit_reads_far_under_the_cells_own_limits(fitted):
    """The rehearsal keeps the cell's own ``fixed_effect_rtol``: the
    readings are about precision, not about size."""
    cell, state, outcome = fitted
    assert state["config"]["fixed_effect_rtol"] == cell["config"][
        "fixed_effect_rtol"]
    check = FIT.reference_check(state, outcome)
    assert check["correct"], check
    found = check["fixed_effect_rel"]
    # the solver's last value is read beside them, for the record alone
    assert set(found) == set(FIT.FIXED_EFFECT_READINGS) | {"value"}
    assert "fixed_effect.value" not in check["compared"]
    for name, limit in state["config"]["fixed_effect_rtol"].items():
        assert check["compared"]["fixed_effect." + name] == {
            "value": found[name], "limit": limit}
        assert 0 <= found[name] < limit / 4, (name, found[name])


def test_the_plain_auc_is_the_programs_to_five_places(fitted):
    """Condition (a) holds a run to ``AUC_ATOL``, 1e-3; a CPU fit of
    the rehearsal's size agrees a hundred times closer, and a plain
    reference that drifted from the program would show here first."""
    _cell, state, outcome = fitted
    check = FIT.reference_check(state, outcome)
    assert abs(check["plain_auc"] - outcome["auc"]) < 1e-5
    assert check["reported_auc"] == outcome["auc"]


def test_the_cold_mix_builds_its_plans(fitted, monkeypatch):
    """``plan_cache: false`` is what makes the plan build part of
    ``fit_s``: no directory in the TrainingConfig, none in the
    environment, whatever the process was started with."""
    cell, state, _outcome = fitted
    assert cell["traffic"]["plan_cache"] is False
    assert state["training_config"].plan_cache_dir is None
    monkeypatch.setenv(FIT.PLAN_CACHE_ENV, "/somewhere")
    again = FIT.prepare(state["config"], state["traffic"],
                        (state["train"], state["valid"], state["truth"]))
    assert again["training_config"].plan_cache_dir is None
    assert FIT.PLAN_CACHE_ENV not in os.environ
    with pytest.raises(ValueError, match="warm-plan"):
        FIT.prepare(state["config"], dict(state["traffic"], plan_cache=True),
                    (state["train"], state["valid"], state["truth"]))


def test_an_outcome_keeps_only_what_is_compared(fitted):
    """The outcome lives through the window: it holds the fixed
    effect's training scores and its solver's last record, not the
    descent's coefficients, the other coordinates' scores or their
    total."""
    _cell, state, outcome = fitted
    names = [c.name for c in state["training_config"].coordinates]
    kept = outcome["descent"]
    assert set(kept) == {"scores", "last"}
    assert list(kept["scores"]) == list(kept["last"]) == names[:1]
    assert kept["scores"][names[0]].shape == (state["train"].n,)
    assert {"value", "grad_norm"} <= set(kept["last"][names[0]])


def test_a_descent_on_the_fit_result_is_taken_where_it_is_found(
        fitted, monkeypatch):
    """The landing place of the read-only field PERF.md's first open
    question asks of ``FitResult``: the PR that adds it may not edit
    the operation, so ``one`` takes it already, before what its own
    subclass kept."""
    from photon_ml_tpu.evaluation import EvaluatorType

    _cell, state, outcome = fitted
    name = state["training_config"].coordinates[0].name
    carried = types.SimpleNamespace(
        scores={name: outcome["descent"]["scores"][name], "another": None},
        history=[{}, {name: {"value": 1.0, "grad_norm": 2.0}}])
    result = types.SimpleNamespace(
        model=outcome["model"], descent=carried,
        evaluations={EvaluatorType.AUC: outcome["auc"]})
    estimator = types.SimpleNamespace(fit=lambda train, valid: [result],
                                      descent=None)
    monkeypatch.setattr(FIT, "_keeping_estimator", lambda config: estimator)
    taken = FIT.one(state)["descent"]
    assert taken["last"] == {name: {"value": 1.0, "grad_norm": 2.0}}
    assert list(taken["scores"]) == [name]
    assert taken["scores"][name] is outcome["descent"]["scores"][name]


def test_bfloat16_contractions_fail_by_the_fixed_effect_alone(fitted):
    """The control: the nearest precision below the configuration's.
    Every other condition still holds, so nothing else would have
    caught it; the forward reading is over its limit tenfold."""
    _cell, state, _outcome = fitted
    with FIT.control("bfloat16", state) as patched:
        check = FIT.reference_check(patched, FIT.one(patched))
    assert not check["correct"]
    assert not check["conditions"][CONDITION] and _others_hold(check), check
    scores = check["compared"]["fixed_effect.scores"]
    assert scores["value"] > 10 * scores["limit"]
    assert scores["value"] == pytest.approx(2.0 ** -8, rel=0.05)
    # and the patch is gone with the context: float32 again
    assert FIT.reference_check(state, FIT.one(state))["correct"]


def test_a_fit_that_hands_nothing_over_is_not_correct(fitted):
    _cell, state, outcome = fitted
    silent = {k: v for k, v in outcome.items() if k != "descent"}
    check = FIT.reference_check(state, silent)
    assert not check["correct"] and _others_hold(check)
    assert all(check["compared"]["fixed_effect." + name]["value"] is None
               for name in state["config"]["fixed_effect_rtol"])
    json.dumps(check["compared"])


@pytest.mark.parametrize("reading", FIT.FIXED_EFFECT_READINGS + ("value",))
def test_each_reading_is_held_to_its_own_limit(fitted, reading):
    """What the descent hands over, altered where it is produced: the
    reading it feeds fails, and only that one; the solver's last
    value, which no configuration may limit (it does not separate
    float32 from bfloat16), is read and not compared."""
    _cell, state, outcome = fitted
    descent = copy.deepcopy(outcome["descent"])
    name = state["training_config"].coordinates[0].name
    whole = FIT.reference_check(state, outcome)
    if reading == "scores":
        descent["scores"][name] = np.asarray(descent["scores"][name]) * 1.001
        expected = 1e-3
    else:
        key = {"value": "value", "gradient_norm": "grad_norm"}[reading]
        descent["last"][name][key] *= 1.01
        # the norm's distance is in units of the gradient at zero
        expected = 1e-2 * (1.0 if reading == "value"
                           else whole["gradient_rel"][name])
    check = FIT.reference_check(state, dict(outcome, descent=descent))
    assert check["fixed_effect_rel"][reading] == pytest.approx(
        expected, rel=0.2)
    if reading not in FIT.FIXED_EFFECT_READINGS:
        assert reading not in state["config"]["fixed_effect_rtol"]
        assert check["correct"]
        assert "fixed_effect." + reading not in check["compared"]
        return
    assert not check["correct"] and _others_hold(check)
    over = {key for key, entry in check["compared"].items()
            if key.startswith("fixed_effect.")
            and entry["value"] > entry["limit"]}
    assert over == {"fixed_effect." + reading}


def test_a_reading_the_configuration_does_not_limit_is_not_compared(fitted):
    _cell, state, outcome = fitted
    config = dict(state["config"],
                  fixed_effect_rtol={"scores": state["config"][
                      "fixed_effect_rtol"]["scores"]})
    check = FIT.reference_check(dict(state, config=config), outcome)
    assert check["correct"]
    assert [key for key in check["compared"]
            if key.startswith("fixed_effect.")] == ["fixed_effect.scores"]


def test_limit_problems_refuse_what_a_bfloat16_pass_could_meet():
    config = manifests.resolve(MANIFEST, FIT_CELLS[0])["config"]
    assert FIT.limit_problems(config) == []
    for rtol in ({"scores": 2.0 ** -11}, {}, {"scores": 1e-6, "tail": 1e-6},
                 {"scores": 1e-6, "value": 1e-6}, {"scores": 0.0}):
        assert FIT.limit_problems(dict(config, fixed_effect_rtol=rtol)), rtol
    assert FIT.limit_problems(dict(config, fixed_effect_rtol_derivation=""))
    assert FIT.limit_problems(dict(config, auc_floor=0.5))
    assert FIT.limit_problems(dict(config, gradient_rtol={"global": 0.01}))


def test_plain_value_and_gradient_norm_against_finite_differences():
    """The reference's own arithmetic: three rows, two columns and an
    intercept, worked out from the definition."""
    indptr, cols, vals = [0, 2, 3, 4], [0, 1, 1, 0], [1.0, 2.0, 1.0, 3.0]
    labels = np.array([1.0, 0.0, 1.0])
    others = np.array([0.1, -0.2, 0.3])
    lam = 0.5

    def value(w):
        z = plain.margins((indptr, cols, vals, w), []) + others
        return float(np.sum(np.logaddexp(0, z) - labels * z)
                     + 0.5 * lam * np.sum(w[:-1] ** 2))

    w = np.array([0.3, -0.7, 0.2])
    block = (indptr, cols, vals, w, lam)
    got, norm, at_zero = plain.coordinate_end(
        block, plain.margins(block[:4], []), others, labels)
    assert got == pytest.approx(value(w), rel=1e-14)
    step = 1e-6
    gradient = [(value(w + step * e) - value(w - step * e)) / (2 * step)
                for e in np.eye(3)]
    assert norm == pytest.approx(np.linalg.norm(gradient), rel=1e-8)
    assert at_zero == pytest.approx(np.linalg.norm(
        [(value(step * e) - value(-step * e)) / (2 * step)
         for e in np.eye(3)]), rel=1e-8)


def test_limits_py_reads_seeds_then_controls_in_one_process():
    cell = manifests.resolve(MANIFEST, FIT_CELLS[0])
    records = list(limits.readings(cell, [7, 8], ["bfloat16"], [7],
                                   rehearsal=True))
    assert [(r["seed"], r["control"]) for r in records] == [
        (7, None), (8, None), (7, "bfloat16")]
    assert [r["correct"] for r in records] == [True, True, False]
    for record in records:
        json.dumps(record)
        # a file of readings says where they are from
        assert (record["platform"], record["rehearsal"]) == ("cpu", True)
        assert record["device_kind"]
        assert set(record["compared"]) >= {
            "fixed_effect." + name
            for name in cell["config"]["fixed_effect_rtol"]}
    with pytest.raises(KeyError, match="no control"):
        with FIT.control("nothing", {}):
            pass


def test_limits_py_reads_a_cells_own_size_on_the_chip_alone(capsys):
    """Without ``--rehearsal`` and without a TPU: no record, exit 2."""
    cell = manifests.resolve(MANIFEST, FIT_CELLS[0])
    with pytest.raises(limits.NoChip):
        next(limits.readings(cell, [7], [], []))
    assert limits.main(["--workload", FIT_CELLS[0], "--seeds", "7"]) == 2
    said = capsys.readouterr()
    assert said.out == "" and "--rehearsal" in said.err


# -- a whole run of run.py, past its look for a chip --------------------------

def _devices():
    """What ``run_cell`` asks of a device, from a stand-in: the CPU
    backend keeps no memory statistics."""
    return [types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"peak_bytes_in_use": 1, "bytes_limit": 2})]


def _rehearsal_cell(cell_name):
    cell = manifests.resolve(MANIFEST, cell_name)
    return dict(cell, config=FIT.rehearsal_config(cell["config"]))


@pytest.mark.parametrize("cell_name", FIT_CELLS)
def test_a_whole_run_is_correct_and_says_what_it_compared(cell_name, capfd):
    result = run_py.run_cell(_rehearsal_cell(cell_name), 2**31 + 5, 0.01,
                             False, _devices())
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 1
    assert set(result["metrics"]) == {
        m["name"] for m in manifests.metrics_of(MANIFEST, "end_to_end",
                                                cell_name)}
    # the numbers compared, each beside its limit, are the last line on
    # standard error and the last key of the result's line
    last = json.loads(capfd.readouterr().err.strip().splitlines()[-1])
    assert last == {"correct": True, "compared": result["compared"]}
    assert set(result["compared"]) >= {"auc_difference", "objective_gap",
                                       "fixed_effect.scores"}


def _altered_where_it_is_produced(monkeypatch):
    """The exported fixed effect is not the trained one: its
    coefficients leave the estimator a hundredth larger."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    export = GameEstimator._to_game_model

    def altered(self, coords, cd):
        model = export(self, coords, cd)
        name = self.config.coordinates[0].name
        part = model.models[name]
        model.models[name] = dataclasses.replace(
            part, coefficients=dataclasses.replace(
                part.coefficients, means=part.coefficients.means * 1.01))
        return model

    monkeypatch.setattr(GameEstimator, "_to_game_model", altered)


@pytest.mark.parametrize("fault", ["bfloat16", "answer_altered"])
def test_a_whole_run_with_the_timed_path_broken_is_not_correct(
        fault, monkeypatch, capfd):
    cell = _rehearsal_cell(FIT_CELLS[0])
    if fault == "bfloat16":
        with FIT.control("bfloat16", None):
            result = run_py.run_cell(cell, 11, 0.01, False, _devices())
    else:
        _altered_where_it_is_produced(monkeypatch)
        result = run_py.run_cell(cell, 11, 0.01, False, _devices())
    assert result["correct"] is False and result["failed"] == 0
    scores = result["compared"]["fixed_effect.scores"]
    assert scores["value"] > 10 * scores["limit"]
    last = json.loads(capfd.readouterr().err.strip().splitlines()[-1])
    assert last["correct"] is False and last["compared"] == result["compared"]


def test_a_tail_left_out_fails_by_the_transposed_reading_alone(monkeypatch):
    """The wide configuration's rehearsal through the GRR layout, whose
    tail class is on its COO path, with that class contributing nothing
    to either contraction: the fit ends at the optimum of the problem
    without its tail, its scores agree with its coefficients, and only
    the plain gradient over all columns says so."""
    from photon_ml_tpu.data import grr

    # one entry does not pay for a column's slots: a tail at 6,000 rows
    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)
    cell = next(manifests.resolve(MANIFEST, name) for name in FIT_CELLS
                if "fields" in manifests.resolve(MANIFEST, name)["config"][
                    "generator"]["params"])
    config = FIT.rehearsal_config(cell["config"])
    config["training_config"]["sparse_layout"] = "GRR"
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    state = FIT.prepare(config, cell["traffic"], data)
    assert FIT.reference_check(state, FIT.one(state))["correct"]
    with FIT.control("no_tail", state) as patched:
        check = FIT.reference_check(patched, FIT.one(patched))
    assert not check["conditions"][CONDITION] and _others_hold(check), check
    compared = check["compared"]
    assert compared["fixed_effect.scores"]["value"] \
        < compared["fixed_effect.scores"]["limit"]
    assert compared["fixed_effect.gradient_norm"]["value"] \
        > 10 * compared["fixed_effect.gradient_norm"]["limit"]
