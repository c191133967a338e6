"""The operation ``fit``: one whole ``GameEstimator(cfg).fit(train,
valid)`` from the in-memory datasets, ended when the validation AUC is
a Python float (so the device is drained).

Only what later PRs will not refactor away is called: the config
loader, ``GameDataset`` (made by the generator) and ``GameEstimator``.
"""

import json
import math
import os
import statistics

import numpy as np

from benchmark.reference import plain

# Same data, same solver, same programs: a repeat's AUC that differs
# from the warm-up's by more than this is a fault, not noise.
REPEAT_ATOL = 1e-3
PLAN_CACHE_ENV = "PHOTON_ML_TPU_PLAN_CACHE"


def prepare(config, traffic, data):
    """Everything ``one`` needs: the TrainingConfig built from the
    configuration file's ``training_config`` under the mix's switches,
    and the generated (train, valid, truth)."""
    from photon_ml_tpu.config import training_config_from_json

    fields = dict(config["training_config"])
    if traffic["plan_cache"]:
        raise ValueError("this operation has no warm-plan mode yet: a mix "
                         "with plan_cache on must bring it")
    # Plan cache off: every fit pays the whole plan build.
    os.environ.pop(PLAN_CACHE_ENV, None)
    fields["plan_cache_dir"] = None
    train, valid, truth = data
    return {"training_config": training_config_from_json(json.dumps(fields)),
            "config": config, "train": train, "valid": valid, "truth": truth}


def one(state):
    """One fit; returns the model and its validation AUC as a float."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    estimator = GameEstimator(state["training_config"])
    result = estimator.fit(state["train"], state["valid"])[0]
    return {"model": result.model,
            "auc": float(result.evaluations[EvaluatorType.AUC])}


def ok(outcome, warm):
    """Whether a fit of the window counts: finite, and the warm-up's
    result again."""
    return (math.isfinite(outcome["auc"])
            and abs(outcome["auc"] - warm["auc"]) <= REPEAT_ATOL)


def summary(outcome):
    return {"auc": outcome["auc"]}


def end_to_end(durations, window_s):
    """The end-to-end metrics this operation gives a cell: ``fit_s``,
    the median wall-clock of the window's whole fits."""
    return {"fit_s": statistics.median(durations)}


def _blocks(model, state, data):
    """The model's exported coefficients beside ``data``'s rows, as
    ``plain`` takes them, by coordinate name: the fixed effect as
    (indptr, cols, vals, w, reg_weight), a random effect as (x, row
    entity, sorted entity ids, coefs [E, p], reg_weight)."""
    from photon_ml_tpu.config import CoordinateKind

    weights = {c["name"]: c["optimizer"]["reg_weight"]
               for c in state["config"]["training_config"]["coordinates"]}
    blocks = {}
    for coord in state["training_config"].coordinates:
        part = model.models[coord.name]
        if coord.kind == CoordinateKind.FIXED_EFFECT:
            rows = data.features[coord.feature_shard]
            blocks[coord.name] = (
                rows.indptr, rows.cols, rows.vals,
                np.asarray(part.coefficients.means, np.float64),
                weights[coord.name])
        else:
            blocks[coord.name] = (
                data.features[coord.feature_shard],
                data.entity_ids[coord.entity_key],
                part.grouping.entity_ids,
                np.asarray(part.all_coefficients(), np.float64),
                weights[coord.name])
    return blocks


def _scores(blocks):
    """By coordinate, the plain scores of its rows (the fixed effect's
    with its intercept)."""
    return {name: (plain.margins(block[:4], []) if block[3].ndim == 1
                   else plain.entity_dot(*block[:4]))
            for name, block in blocks.items()}


def _gradients(state, blocks, scores, labels):
    """By coordinate, (gradient norm, the same at zero coefficients) at
    the state the coordinate's solver saw.  In a one-sweep fit that
    state is known from the exported model: the coordinates trained
    before it at their final coefficients, those after it at zero.
    With more sweeps only the last coordinate's is."""
    fields = state["config"]["training_config"]
    order = fields["update_sequence"]
    one_sweep = fields["n_iterations"] == 1
    out = {}
    for name in (order if one_sweep else order[-1:]):
        seen = (order[:order.index(name)] if one_sweep
                else [other for other in order if other != name])
        others = sum((scores[other] for other in seen),
                     np.zeros(len(labels)))
        out[name] = plain.coordinate_gradient(blocks[name], scores[name],
                                              others, labels)
    return out


def reference_check(state, outcome):
    """``correct`` and what it rests on, from the plain reference."""
    config = state["config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    blocks = _blocks(outcome["model"], state, train)
    scores = _scores(blocks)
    fixed = [b for b in blocks.values() if b[3].ndim == 1][0]
    random_effects = [b for b in blocks.values() if b[3].ndim == 2]
    out = plain.check(
        valid_margins=sum(_scores(_blocks(outcome["model"], state,
                                          valid)).values()),
        valid_labels=valid.labels,
        train_margins=sum(scores.values()),
        train_labels=train.labels,
        train_penalty=plain.penalty(fixed, random_effects),
        true_train_margins=truth["train_margins"],
        gradients=_gradients(state, blocks, scores, train.labels),
        reported_auc=outcome["auc"],
        auc_floor=config["auc_floor"],
        objective_gap=config["objective_gap"],
        gradient_rtol=config["gradient_rtol"])
    out["auc_of_true_margins"] = plain.auc(truth["valid_margins"],
                                           valid.labels)
    return out
