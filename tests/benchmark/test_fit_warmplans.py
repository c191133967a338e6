"""The operation ``fit_warmplans`` at ``game5-kdd12``'s rehearsal size on
the CPU: the first fit of a state builds the GRR plans and saves them,
every later fit loads them and refits the same result, and a later fit
whose plans did not come from the cache fails.  The harness's own
parametrised tests (``test_harness.py``) rehearse the cell whole,
damaged and cut short.  Nothing here is a performance number.
"""

import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402

MANIFEST = manifests.load_manifest()
CELL = "game5-kdd12.fit-warmplans"
OPERATION = manifests.load_module(os.path.join(
    REPO, "benchmark", "operations", "fit_warmplans.py"))


def _state():
    cell = manifests.resolve(MANIFEST, CELL)
    config = OPERATION.rehearsal_config(cell["config"])
    data = manifests.load_module(cell["generator_path"]).make(
        5, **config["generator"]["params"])
    return OPERATION.prepare(config, cell["traffic"], data)


def _plan_stages(state):
    """(outcome, the fit's grr_plan_build, plan_cache_load and
    plan_cache_save stages as (name, counts))."""
    with OPERATION._stages_recorded([]) as stages:
        outcome = OPERATION.one(state)
    return outcome, [(s.name, dict(s.counts)) for s in stages
                     if s.name in ("grr_plan_build", "plan_cache_load",
                                   "plan_cache_save")]


def test_the_cell_is_game5_kdd12_under_its_own_limits():
    cell = manifests.resolve(MANIFEST, CELL)
    cold = manifests.resolve(MANIFEST, "game5-kdd12.fit-cold")
    assert cell["config"] == cold["config"]
    assert cell["traffic"]["operation"] == "fit_warmplans"
    assert cell["traffic"]["plan_cache"] is True
    assert OPERATION.LIMIT_KEYS == OPERATION.fit.LIMIT_KEYS
    assert OPERATION.limit_problems(cell["config"]) == []
    with pytest.raises(ValueError, match="plan_cache on"):
        OPERATION.prepare(cell["config"], cold["traffic"], None)


def test_the_first_fit_builds_and_saves_and_the_next_ones_load():
    state = _state()
    plans = state["training_config"].plan_cache_dir
    assert plans.startswith(OPERATION.PLAN_ROOT) and os.listdir(plans) == []
    first, stages = _plan_stages(state)
    # (in the order they were entered)
    assert [name for name, _c in stages] == [
        "grr_plan_build", "plan_cache_load", "plan_cache_save"]
    assert stages[0][1]["cache_hit"] == 0 and os.listdir(plans)
    for _fit in range(2):
        again, stages = _plan_stages(state)
        assert [name for name, _c in stages] == [
            "grr_plan_build", "plan_cache_load"]
        assert stages[0][1]["cache_hit"] == 1 and stages[1][1]["bytes"] > 0
        assert again["auc"] == first["auc"]
    assert state["fits"] == 3


def test_a_later_fit_that_builds_its_plans_fails():
    state = _state()
    OPERATION.one(state)
    shutil.rmtree(state["training_config"].plan_cache_dir)
    with pytest.raises(RuntimeError, match="plan cache missed"):
        OPERATION.one(state)
