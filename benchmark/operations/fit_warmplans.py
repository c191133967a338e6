"""The operation ``fit_warmplans``: ``fit`` (one whole
``GameEstimator(cfg).fit(train, valid)``, ended when the validation AUC
is a Python float) with the GRR plan cache on, in a directory of this
run.  The first fit of a state (the harness's warm-up) builds the
fixed effect's plans and saves them; every later fit refits the same
dataset and loads them.  What is timed is then everything but the plan
build: the cache's load and transfer, entity grouping, placement,
solves and validation.

A later fit that built a plan, or loaded none, raises: the cell can
never time a cold build by accident (a fit that raises is a failed
operation of the window).  Which path ran is read from the fit's own
``grr_plan_build`` stages (their ``cache_hit`` count), recorded while
the fit runs, with or without a profiler.

Everything else is ``operations/fit.py``'s, loaded by its file: the
check, its limits, what counts as a repeat, ``fit_s``.  The interface
an operation gives the harness is in ``benchmark/README.md``.
"""

import atexit
import contextlib
import copy
import json
import os
import shutil
import tempfile

from benchmark.harness import manifest as manifests

fit = manifests.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit.py"))

(ok, not_ok, summary, end_to_end, reference_check, limit_problems,
 damaged) = (
    fit.ok, fit.not_ok, fit.summary, fit.end_to_end, fit.reference_check,
    fit.limit_problems, fit.damaged)
LIMIT_KEYS = fit.LIMIT_KEYS
# Each run's plans, removed when the process ends.
PLAN_ROOT = os.path.join(manifests.BENCH_DIR, "out", "plans")


def _plan_dir():
    os.makedirs(PLAN_ROOT, exist_ok=True)
    path = tempfile.mkdtemp(prefix="plans-", dir=PLAN_ROOT)
    atexit.register(shutil.rmtree, path, True)
    return path


def rehearsal_config(config):
    """``fit.rehearsal_config``, with the GRR layout asked for: off the
    TPU the layout's AUTO picks ELL, which has no plans to cache."""
    config = fit.rehearsal_config(config)
    config["training_config"]["sparse_layout"] = "GRR"
    return config


def prepare(config, traffic, data):
    """``fit.prepare``'s state, its TrainingConfig's ``plan_cache_dir``
    a new directory of this run."""
    from photon_ml_tpu.config import training_config_from_json

    if not traffic["plan_cache"]:
        raise ValueError("fit_warmplans runs a mix with plan_cache on")
    state = fit.prepare(config, dict(traffic, plan_cache=False), data)
    fields = dict(config["training_config"], plan_cache_dir=_plan_dir())
    return dict(state, traffic=traffic, fits=0,
                training_config=training_config_from_json(
                    json.dumps(fields)))


@contextlib.contextmanager
def _stages_recorded(into):
    """Every ``telemetry.stage`` entered while the context lasts is
    appended to ``into`` (the stage itself, so its counts are read once
    it has closed)."""
    from photon_ml_tpu import telemetry

    stage = telemetry.stage

    def recorded(name, *args, **counts):
        into.append(stage(name, *args, **counts))
        return into[-1]

    telemetry.stage = recorded
    try:
        yield into
    finally:
        telemetry.stage = stage


def one(state):
    """``fit.one``; after the state's first fit, refused unless every
    plan of the fit came from the cache."""
    with _stages_recorded([]) as stages:
        outcome = fit.one(state)
    builds = [s.counts.get("cache_hit") for s in stages
              if s.name == "grr_plan_build"]
    if state["fits"] and (not builds or not all(builds)):
        raise RuntimeError(
            f"a warm-plan fit built its GRR plans (cache_hit {builds} in "
            "its grr_plan_build stages): the plan cache missed")
    state["fits"] += 1
    return outcome


def _with_iterations(state, iterations_of):
    """``state`` again, plans cold in a new directory, with every
    coordinate's ``max_iters`` put through ``iterations_of``."""
    config = copy.deepcopy(state["config"])
    for coordinate in config["training_config"]["coordinates"]:
        optimizer = coordinate["optimizer"]
        optimizer["max_iters"] = iterations_of(optimizer["max_iters"])
    return prepare(config, state["traffic"],
                   (state["train"], state["valid"], state["truth"]))


def cut_short(state, outcome):
    """As ``fit.cut_short``, the short fit through this operation."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-3
    config["gradient_rtol"] = {name: 2 * value for name, value
                               in whole["gradient_rel"].items()}
    tight = dict(state, config=config)
    return (tight,
            _with_iterations(tight, lambda _n: fit.SHORT_ITERATIONS),
            ["auc_agrees"], ["objective_reached", "gradient_small"])
