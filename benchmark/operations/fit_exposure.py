"""The operation ``fit_exposure``: ``fit`` (one whole
``GameEstimator(cfg).fit(train, valid)``, ended when the validation
metric is a Python float) of a Poisson model of counts whose rows carry
an exposure: the dataset's ``offsets`` hold its log, the fixed effect is
an elastic net, and the evaluator is ``POISSON_LOSS``.

What is the same is ``operations/fit.py``'s, loaded by its file: the
mix's switches, ``fit_s``, the exported blocks beside the rows, what
each solver saw of the other coordinates, the fixed effect's own
readings, the iteration and bfloat16 controls.  Its own: a program
whose training does not take a dataset's offsets is refused before any
data is made; the descent is read from ``FitResult.descent`` and from
nowhere else; and ``correct`` is ``reference/poisson_enet.py``'s six
conditions, every one with the exposure in: a loss where ``fit`` has an
AUC, a KKT residual where it has a gradient, and the export's exact
zeros.

The interface an operation gives the harness is in
``benchmark/README.md``.
"""

import contextlib
import copy
import dataclasses
import inspect
import math
import os

import numpy as np

from benchmark.harness import manifest as manifests
from benchmark.reference import poisson_enet

fit = manifests.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit.py"))

end_to_end = fit.end_to_end

LIMIT_KEYS = ("loss_gain_floor", "objective_gap", "optimality_rtol",
              "fixed_effect_rtol", "zero_rtol")
# Same data, same solver, same programs: a repeat's loss that differs
# from the warm-up's by more than this share of it is a fault, not noise.
REPEAT_RTOL = 1e-3
# A solve that leaves half of its residual at zero has hardly run.
OPTIMALITY_RTOL_MOST = 0.5
# More than a hundredth of the coordinates that should be exact zeros
# left off zero is no L1 solve (a fit without the L1 term leaves every
# column that a training row touches off zero).
ZERO_RTOL_MOST = 0.05


def refuse_a_training_that_drops_offsets():
    """Stop where the program's coordinate descent takes no dataset
    offsets: such a program trains without the exposure and is
    validated with it, and no condition here would mean anything."""
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent

    if "offsets" not in inspect.signature(run_coordinate_descent).parameters:
        raise RuntimeError(
            "run_coordinate_descent takes no `offsets`: this program's "
            "training drops a dataset's offsets (the log exposure of a "
            "count), and fit_exposure does not run on it")


def _elastic_net(config):
    """(name, alpha) of the configuration's one fixed effect."""
    (fixed,) = [c for c in config["training_config"]["coordinates"]
                if c["kind"] == "FIXED_EFFECT"]
    return fixed["name"], fixed["optimizer"]["elastic_net_alpha"]


def limit_problems(config):
    """What is wrong with the limits a configuration states for this
    operation, as a list of sentences; empty when nothing is."""
    problems = [f"{key}: missing, or without its {key}_derivation"
                for key in LIMIT_KEYS
                if config.get(key) is None
                or not config.get(key + "_derivation")]
    if problems:
        return problems
    names = [c["name"] for c in config["training_config"]["coordinates"]]
    if config["training_config"]["n_iterations"] != 1:
        problems.append("n_iterations: the state a solver saw is read off "
                        "the export of a one-sweep fit (fit._seen)")
    if not 0 < config["loss_gain_floor"] < 1:
        problems.append("loss_gain_floor: not a gain in Poisson loss a row "
                        "over the one-number model, between 0 and 1")
    if not abs(config["objective_gap"]) < 0.1:
        problems.append("objective_gap: 0.1 a row or more of Poisson loss")
    if sorted(config["optimality_rtol"]) != sorted(names):
        problems.append("optimality_rtol: not one limit a coordinate")
    if not all(0 < v < OPTIMALITY_RTOL_MOST
               for v in config["optimality_rtol"].values()):
        problems.append("optimality_rtol: a limit outside "
                        f"(0, {OPTIMALITY_RTOL_MOST})")
    rtol = config["fixed_effect_rtol"]
    if not rtol or set(rtol) - set(fit.FIXED_EFFECT_READINGS):
        problems.append("fixed_effect_rtol: limits some of "
                        f"{fit.FIXED_EFFECT_READINGS} and nothing else")
    if not all(0 < v < fit.FIXED_EFFECT_RTOL_MOST for v in rtol.values()):
        problems.append("fixed_effect_rtol: a limit outside (0, 2**-11), "
                        "which a bfloat16 contraction could pass")
    if not 0 <= config["zero_rtol"] < ZERO_RTOL_MOST:
        problems.append(f"zero_rtol: outside [0, {ZERO_RTOL_MOST})")
    return problems


def rehearsal_config(config):
    """``config`` with its generator at ``rehearsal_params`` and the
    limits a tiny CPU fit is held to.  A few thousand rows over 2e5
    columns do not always beat the one-number model on a hundred
    validation rows: the tiny fit states no gain (``loss_gain_floor``
    None) and has to beat the loss of zero coefficients, one click an
    impression, which tells a model from none; its gap and residuals
    only tell a solve from none, and its zeros an L1 solve from a
    dense one (tiny sound fits read 0.004 to 0.005 there, a fit
    without the L1 term 0.12).  The fixed effect's limits are about
    precision, not about how far a solve got, and stay the cell's."""
    config = copy.deepcopy(config)
    config["generator"]["params"].update(config["rehearsal_params"])
    config["loss_gain_floor"] = None
    config["zero_rtol"] = 0.02
    config["objective_gap"] = 0.5
    config["optimality_rtol"] = {
        c["name"]: 0.5 for c in config["training_config"]["coordinates"]}
    return config


def prepare(config, traffic, data):
    refuse_a_training_that_drops_offsets()
    return fit.prepare(config, traffic, data)


def one(state):
    """One fit: the model, its validation Poisson loss as a float, and
    of the descent each coordinate's training scores as it ended with
    them (the device arrays, untouched) and each solver's last record."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    result = GameEstimator(state["training_config"]).fit(
        state["train"], state["valid"])[0]
    descent = result.descent
    return {"model": result.model,
            "loss": float(result.evaluations[EvaluatorType.POISSON_LOSS]),
            "descent": {"scores": dict(descent.scores),
                        "last": {name: dict(record) for name, record
                                 in descent.history[-1].items()}}}


def ok(outcome, warm):
    """Whether a fit of the window counts: finite, and the warm-up's
    result again."""
    return (math.isfinite(outcome["loss"])
            and abs(outcome["loss"] - warm["loss"])
            <= REPEAT_RTOL * abs(warm["loss"]))


def not_ok(outcome):
    """Outcomes that ``ok`` must refuse beside ``outcome``."""
    return [dict(outcome, loss=float("nan")),
            dict(outcome, loss=outcome["loss"] * (1 + 10 * REPEAT_RTOL))]


def summary(outcome):
    """The loss, and what the fixed effects' solves say they paid
    (``ls_trials``, ``forward_passes``: OWL-QN's, which move ``fit_s``
    with the seed)."""
    said = {"poisson_loss": outcome["loss"]}
    for record in outcome["descent"]["last"].values():
        for key in ("ls_trials", "forward_passes"):
            if key in record:
                said[key] = said.get(key, 0) + record[key]
    return said


def _zeros(outcome, name, w, inside):
    """What condition (f) reads: the share of the coordinates the
    reference's KKT calls zero (``inside``) that the export holds off
    zero, the export's own count of nonzero coefficients, and the
    program's (its fixed effect's last record)."""
    return {"called_zero": int(inside.sum()),
            "not_zero": float(np.count_nonzero(w[inside]))
            / max(1, int(inside.sum())),
            "exported": int(np.count_nonzero(w)),
            "counted": outcome["descent"]["last"][name].get(
                "nonzero_coefficients")}


def reference_check(state, outcome):
    """``correct`` and what it rests on, from the plain reference, the
    exposures taken from the generator's truth and not from the
    dataset the program was handed."""
    config = state["config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    name, alpha = _elastic_net(config)
    exposure = np.log(truth["train_exposure"])
    blocks = fit._blocks(outcome["model"], state, train)
    scores = fit._scores(blocks)
    ends, optimality = {}, {}
    for coordinate, others in fit._seen(state, scores,
                                        len(train.labels)).items():
        seen = exposure + others
        if coordinate == name:
            end = poisson_enet.fixed_effect_end(
                blocks[name], scores[name], seen, train.labels, alpha)
            ends[name] = (end["value"], end["kkt_norm"],
                          end["kkt_norm_at_zero"])
            optimality[name] = ends[name][1:]
            inside = end["inside"]
        else:
            optimality[coordinate] = poisson_enet.random_effect_end(
                blocks[coordinate], scores[coordinate], seen, train.labels)
    fixed = {name: blocks[name]}
    if config["loss_gain_floor"] is None:    # ``rehearsal_config``
        baseline, floor = poisson_enet.mean_poisson_loss(
            np.log(truth["valid_exposure"]), valid.labels), 0.0
    else:
        baseline, floor = poisson_enet.loss_of_the_pooled_rate(
            train.labels, truth["train_exposure"], valid.labels,
            truth["valid_exposure"]), config["loss_gain_floor"]
    out = poisson_enet.check(
        valid_margins=np.log(truth["valid_exposure"]) + sum(
            fit._scores(fit._blocks(outcome["model"], state,
                                    valid)).values()),
        valid_labels=valid.labels,
        reported_loss=outcome["loss"],
        train_margins=exposure + sum(scores.values()),
        train_labels=train.labels,
        train_penalty=poisson_enet.penalty(
            blocks[name], [b for other, b in blocks.items()
                           if other != name], alpha),
        true_train_margins=exposure + truth["train_margins"],
        optimality=optimality,
        fixed_effect=fit._fixed_effect_readings(outcome, fixed, scores,
                                                ends),
        baseline_loss=baseline,
        zeros=_zeros(outcome, name, blocks[name][3], inside),
        limits=dict({key: config[key] for key in LIMIT_KEYS},
                    loss_gain_floor=floor))
    out["loss_of_true_rates"] = poisson_enet.mean_poisson_loss(
        np.log(truth["valid_exposure"]) + truth["valid_margins"],
        valid.labels)
    return out


# -- what the rehearsals and the limits' readings take from here ---------------

def damaged(state, outcome):
    """``fit``'s (the last coordinate's coefficients zeroed): the model
    no longer scores as the program said it did."""
    return [(what, bad, ["loss_agrees"])
            for what, bad, _failing in fit.damaged(state, outcome)]


def cut_short(state, outcome):
    """As ``fit.cut_short``, through this operation's check."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-3
    config["optimality_rtol"] = {name: 2 * value for name, value
                                 in whole["optimality_rel"].items()}
    tight = dict(state, config=config)
    return (tight,
            fit._with_iterations(tight, lambda _n: fit.SHORT_ITERATIONS),
            ["loss_agrees"], ["objective_reached", "optimal_with_exposure"])


def _trained_without_l1(state):
    """``state`` with the fixed effect's elastic net at alpha 0 in the
    TrainingConfig alone: the check keeps the configuration's."""
    config = copy.deepcopy(state["config"])
    for coordinate in config["training_config"]["coordinates"]:
        if coordinate["kind"] == "FIXED_EFFECT":
            coordinate["optimizer"]["elastic_net_alpha"] = 0.0
    prepared = fit.prepare(config, state["traffic"],
                           (state["train"], state["valid"], state["truth"]))
    return dict(prepared, config=state["config"])


@contextlib.contextmanager
def control(name, state):
    """The fits that must NOT be ``correct``:

    ``offsets_dropped``: the same fit of the training rows with their
    offsets taken off, validated with them: what the program did before
    it carried them.  (c), by a wide margin.
    ``l1_dropped``: the fixed effect trained at alpha 0, every weight on
    the L2 term.  (c) and (f).
    ``bfloat16``: ``fit``'s: every fixed-effect contraction's result
    rounded to bfloat16 by ``lax.reduce_precision``.  (e).
    ``two_iterations``: every solve stopped after two iterations.  (c)."""
    if name == "offsets_dropped":
        yield dict(state, train=dataclasses.replace(state["train"],
                                                    offsets=None))
    elif name == "l1_dropped":
        yield _trained_without_l1(state)
    elif name in ("bfloat16", "two_iterations"):
        with fit.control(name, state) as controlled:
            yield controlled
    else:
        raise KeyError(f"no control {name!r}")


CONTROLS = ("offsets_dropped", "l1_dropped", "bfloat16", "two_iterations")
