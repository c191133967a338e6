"""The operation ``fit_ctr``: ``fit`` (one whole
``GameEstimator(cfg).fit(train, valid)``, ended when the validation
metric is a Python float) of a least-squares model of click-through
rates whose rows are weighted by their impressions, every coordinate
solved by TRON, and the evaluator ``RMSE``.

What is the same is ``operations/fit.py``'s, loaded by its file: the
mix's switches, ``fit_s``, the exported blocks beside the rows, what
each solver saw of the other coordinates, the fixed effect's own
readings, the bfloat16 control.  Its own: a program whose
configuration cannot state TRON's inner cap is refused before any data
is made; the descent is read from ``FitResult.descent``; and
``correct`` is ``reference/least_squares.py``'s five conditions, every
one with the impressions in: an RMSE where ``fit`` has an AUC, and a
squared loss where it has a log-loss.

The interface an operation gives the harness is in
``benchmark/README.md``.
"""

import contextlib
import copy
import dataclasses
import math
import os

import numpy as np

from benchmark.harness import manifest as manifests
from benchmark.reference import least_squares, plain

fit = manifests.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit.py"))

end_to_end = fit.end_to_end

LIMIT_KEYS = ("rmse_gain_floor", "objective_gap", "gradient_rtol",
              "fixed_effect_rtol")
# Same data, same solver, same programs: a repeat's RMSE that differs
# from the warm-up's by more than this share of it is a fault, not noise.
REPEAT_RTOL = 1e-4
# A solve that leaves half of its gradient at zero has hardly run.
GRADIENT_RTOL_MOST = 0.5
# The squared loss of a rate per impression is about p (1 - p) / 2, a
# hundredth at a click share of 3.5 %: a gap of half of that is no fit.
OBJECTIVE_GAP_MOST = 0.005
# An RMSE gain of a tenth of a rate is more than any CTR model makes.
RMSE_GAIN_FLOOR_MOST = 0.1
# What the fixed effect's last record says its TRON solve paid.
COUNTS = ("solver_iterations", "cg_steps", "hvp_passes", "forward_passes")


def refuse_a_program_without_cg_settings():
    """Stop where a coordinate's ``OptimizerSettings`` has no
    ``cg_max_iters``: such a program runs TRON's inner loop at its
    built-in cap of 50 products an outer iteration, which at the cell's
    width takes minutes a fit, and its configuration loader refuses the
    cell's."""
    from photon_ml_tpu.config import OptimizerSettings

    if "cg_max_iters" not in {f.name for f in
                              dataclasses.fields(OptimizerSettings)}:
        raise RuntimeError(
            "OptimizerSettings has no `cg_max_iters`: this program's "
            "configuration cannot cap TRON's inner loop, and fit_ctr "
            "does not run on it")


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
    if not 0 < config["rmse_gain_floor"] < RMSE_GAIN_FLOOR_MOST:
        problems.append("rmse_gain_floor: not a gain in RMSE over the "
                        f"one-number model in (0, {RMSE_GAIN_FLOOR_MOST})")
    if not abs(config["objective_gap"]) < OBJECTIVE_GAP_MOST:
        problems.append("objective_gap: half a hundredth of squared loss an "
                        "impression or more")
    if sorted(config["gradient_rtol"]) != sorted(names):
        problems.append("gradient_rtol: not one limit a coordinate")
    if not all(0 < v < GRADIENT_RTOL_MOST
               for v in config["gradient_rtol"].values()):
        problems.append("gradient_rtol: a limit outside "
                        f"(0, {GRADIENT_RTOL_MOST})")
    rtol = config["fixed_effect_rtol"]
    if not rtol or set(rtol) - set(fit.FIXED_EFFECT_READINGS):
        problems.append("fixed_effect_rtol: limits some of "
                        f"{fit.FIXED_EFFECT_READINGS} and nothing else")
    if not all(0 < v < fit.FIXED_EFFECT_RTOL_MOST for v in rtol.values()):
        problems.append("fixed_effect_rtol: a limit outside (0, 2**-11), "
                        "which a bfloat16 contraction could pass")
    return problems


def rehearsal_config(config):
    """``config`` with its generator at ``rehearsal_params`` and the
    limits a tiny CPU fit is held to.  A few thousand rows over 2e5
    columns need not beat the one-number model on a hundred validation
    rows: the tiny fit states no gain (``rmse_gain_floor`` None) and
    has to beat the RMSE of zero coefficients, a rate of 0, which tells
    a model from none; its gap and gradients tell a solve from one cut
    short (tiny sound fits read gradients of 4e-7 to 5e-4 and gaps
    under 0, one Newton step 0.03 to 0.07, dropped weights 0.07 to
    0.5).  The fixed effect's limits are about precision, not about how
    far a solve got, and stay the cell's."""
    config = copy.deepcopy(config)
    config["generator"]["params"].update(config["rehearsal_params"])
    config["rmse_gain_floor"] = None
    config["objective_gap"] = 0.001
    config["gradient_rtol"] = {
        c["name"]: 0.02 for c in config["training_config"]["coordinates"]}
    return config


def prepare(config, traffic, data):
    refuse_a_program_without_cg_settings()
    return fit.prepare(config, traffic, data)


def one(state):
    """One fit: the model, its validation RMSE as a float, and of the
    descent the fixed effect's training scores as it ended with them
    (the device array, untouched) and its solver's last record."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    result = GameEstimator(state["training_config"]).fit(
        state["train"], state["valid"])[0]
    return {"model": result.model,
            "rmse": float(result.evaluations[EvaluatorType.RMSE]),
            "descent": fit._handed_over(result.descent,
                                        state["training_config"])}


def ok(outcome, warm):
    """Whether a fit of the window counts: finite, and the warm-up's
    result again."""
    return (math.isfinite(outcome["rmse"])
            and abs(outcome["rmse"] - warm["rmse"])
            <= REPEAT_RTOL * warm["rmse"])


def not_ok(outcome):
    """Outcomes that ``ok`` must refuse beside ``outcome``."""
    return [dict(outcome, rmse=float("nan")),
            dict(outcome, rmse=outcome["rmse"] * (1 + 10 * REPEAT_RTOL))]


def summary(outcome):
    """The RMSE, and what the fixed effect's TRON solve says it paid
    (its iterations, CG steps, Hessian-vector products and forward
    contractions: what ``fit_s`` follows)."""
    said = {"rmse": outcome["rmse"]}
    for record in (outcome["descent"] or {}).get("last", {}).values():
        said.update({key: record[key] for key in COUNTS if key in record})
    return said


def _scores(blocks):
    """By coordinate, the plain scores of its rows (the fixed effect's
    with its intercept), the fixed effect's a block of rows at a
    time."""
    return {name: (least_squares.fixed_scores(block) if block[3].ndim == 1
                   else plain.entity_dot(*block[:4]))
            for name, block in blocks.items()}


def reference_check(state, outcome):
    """``correct`` and what it rests on, from the plain reference, the
    impressions taken from the generator's truth and not from the
    dataset the program was handed."""
    config = state["config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    weights = truth["train_weights"]
    labels = np.asarray(train.labels, np.float64)
    blocks = fit._blocks(outcome["model"], state, train)
    scores = _scores(blocks)
    ends, gradients = {}, {}
    for name, seen in fit._seen(state, scores, len(labels)).items():
        if blocks[name][3].ndim == 1:
            ends[name] = least_squares.fixed_effect_end(
                blocks[name], scores[name], seen, labels, weights)
            gradients[name] = ends[name][1:]
        else:
            gradients[name] = least_squares.random_effect_end(
                blocks[name], scores[name], seen, labels, weights)
    fixed = {name: block for name, block in blocks.items()
             if block[3].ndim == 1}
    (fixed_block,) = fixed.values()
    valid_margins = sum(_scores(fit._blocks(outcome["model"], state,
                                            valid)).values())
    if config["rmse_gain_floor"] is None:    # ``rehearsal_config``
        baseline, floor = least_squares.weighted_rmse(
            np.zeros(valid.n), valid.labels, truth["valid_weights"]), 0.0
    else:
        baseline, floor = least_squares.rmse_of_the_pooled_rate(
            labels, weights, valid.labels,
            truth["valid_weights"]), config["rmse_gain_floor"]
    out = least_squares.check(
        valid_margins=valid_margins,
        valid_labels=valid.labels,
        valid_weights=truth["valid_weights"],
        reported_rmse=outcome["rmse"],
        train_margins=sum(scores.values()),
        train_labels=labels,
        train_weights=weights,
        train_penalty=least_squares.penalty(
            fixed_block, [b for b in blocks.values() if b[3].ndim == 2]),
        true_train_margins=truth["train_margins"],
        gradients=gradients,
        fixed_effect=fit._fixed_effect_readings(outcome, fixed, scores,
                                                ends),
        baseline_rmse=baseline,
        limits=dict({key: config[key] for key in LIMIT_KEYS},
                    rmse_gain_floor=floor))
    out["rmse_of_true_rates"] = least_squares.weighted_rmse(
        truth["valid_margins"], valid.labels, truth["valid_weights"])
    return out


# -- what the rehearsals and the limits' readings take from here ---------------

def damaged(state, outcome):
    """``fit``'s (the last coordinate's coefficients zeroed): the model
    no longer scores as the program said it did."""
    return [(what, bad, ["rmse_agrees"])
            for what, bad, _failing in fit.damaged(state, outcome)]


def cut_short(state, outcome):
    """As ``fit.cut_short``, through this operation's check: limits
    just above the whole fit, and every solve stopped after two outer
    iterations."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-6
    config["gradient_rtol"] = {name: 2 * value for name, value
                               in whole["gradient_rel"].items()}
    tight = dict(state, config=config)
    return (tight,
            fit._with_iterations(tight, lambda _n: fit.SHORT_ITERATIONS),
            ["rmse_agrees"], ["objective_reached", "optimal"])


@contextlib.contextmanager
def control(name, state):
    """The fits that must NOT be ``correct``:

    ``bfloat16``: ``fit``'s: every fixed-effect contraction's result
    rounded to bfloat16 by ``lax.reduce_precision``.  (e).
    ``weights_dropped``: the training rows weigh 1 each, the check keeps
    the impressions: what a program that drops a dataset's weights
    would fit.  (b) or (c).
    ``one_newton_step``: every coordinate's TRON stopped after one outer
    iteration.  (c)."""
    if name == "bfloat16":
        with fit.control(name, state) as controlled:
            yield controlled
    elif name == "weights_dropped":
        yield dict(state, train=dataclasses.replace(state["train"],
                                                    weights=None))
    elif name == "one_newton_step":
        yield fit._with_iterations(state, lambda _n: 1)
    else:
        raise KeyError(f"no control {name!r}")


CONTROLS = ("bfloat16", "weights_dropped", "one_newton_step")
