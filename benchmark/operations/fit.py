"""The operation ``fit``: one whole ``GameEstimator(cfg).fit(train,
valid)`` from the in-memory datasets, ended when the validation AUC is
a Python float (so the device is drained).

Only what later PRs will not refactor away is called: the config
loader, ``GameDataset`` (made by the generator) and ``GameEstimator``;
and, until ``FitResult`` hands the coordinate descent's result over
itself (``_Keeping`` below), the one private method that is given it.

The interface an operation gives the harness and its rehearsals is in
``benchmark/README.md``.
"""

import contextlib
import copy
import dataclasses
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

# What a configuration run by this operation states, each beside its
# ``<key>_derivation``: the limits ``reference_check`` holds a fit to.
LIMIT_KEYS = ("auc_floor", "objective_gap", "gradient_rtol",
              "fixed_effect_rtol")
# A solve that leaves half of its gradient at zero has hardly run (two
# iterations of the cells' fixed effect leave 4 to 7 % of it, their 30
# anywhere between 0.6 and 13 %: my chip runs, PR 34).
GRADIENT_RTOL_MOST = 0.5
# The readings of the fixed effect that a configuration limits.  The
# solver's last objective value is read beside them, for the record
# alone: it does not tell float32 from bfloat16 (my chip runs, PR 34:
# float32 up to 9.4e-6, bfloat16 1.0e-5 to 2.5e-5).
FIXED_EFFECT_READINGS = ("scores", "gradient_norm")
# bfloat16 keeps 8 bits: a contraction's result rounded to it is off by
# up to 2**-9 of itself.  A fixed-effect limit at or over a quarter of
# that could pass one.
FIXED_EFFECT_RTOL_MOST = 2.0 ** -11


def limit_problems(config):
    """What is wrong with the limits a configuration states for this
    operation, as a list of sentences; empty when nothing is."""
    problems = [f"{key}: missing, or without its {key}_derivation"
                for key in LIMIT_KEYS
                if key not in config or not config.get(key + "_derivation")]
    if problems:
        return problems
    names = [c["name"] for c in config["training_config"]["coordinates"]]
    if not 0.5 < config["auc_floor"] < 1.0:
        problems.append("auc_floor: not between a coin's 0.5 and 1")
    if not abs(config["objective_gap"]) < 0.1:
        problems.append("objective_gap: 0.1 a row or more of log-loss")
    if sorted(config["gradient_rtol"]) != sorted(names):
        problems.append("gradient_rtol: not one limit a coordinate")
    if not all(0 < v < GRADIENT_RTOL_MOST
               for v in config["gradient_rtol"].values()):
        problems.append("gradient_rtol: a limit outside "
                        f"(0, {GRADIENT_RTOL_MOST})")
    rtol = config["fixed_effect_rtol"]
    if not rtol or set(rtol) - set(FIXED_EFFECT_READINGS):
        problems.append("fixed_effect_rtol: limits some of "
                        f"{FIXED_EFFECT_READINGS} and nothing else")
    if not all(0 < v < FIXED_EFFECT_RTOL_MOST for v in rtol.values()):
        problems.append("fixed_effect_rtol: a limit outside (0, 2**-11), "
                        "which a bfloat16 contraction could pass")
    return problems


def rehearsal_config(config):
    """``config`` with its generator at ``rehearsal_params`` and the
    limits a tiny CPU fit is held to.  A tiny problem learns less than
    the cell's and is fitted looser: its floor only tells a model from
    a coin, its gap and gradients only a solve from none (the cell's
    own limits are for its own size).  The fixed effect's limits are
    about precision, not about how far a solve got, and stay the
    cell's."""
    config = copy.deepcopy(config)
    config["generator"]["params"].update(config["rehearsal_params"])
    config["auc_floor"] = 0.55
    config["objective_gap"] = 0.5
    config["gradient_rtol"] = {
        c["name"]: 0.5 for c in config["training_config"]["coordinates"]}
    return config


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
            "config": config, "traffic": traffic,
            "train": train, "valid": valid, "truth": truth}


def _keeping_estimator(training_config):
    """A ``GameEstimator`` that keeps the coordinate descent's result
    (``CoordinateDescentResult``) where the model is exported from it.
    Nothing is computed and nothing leaves the device for it.  It
    rests on a private method: a ``FitResult`` that carries ``descent``
    itself ends that (PERF.md, Open questions, first), and ``one``
    takes the field where it finds it, since the PR that adds it may
    not edit this file."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    class _Keeping(GameEstimator):
        descent = None

        def _to_game_model(self, coords, cd):
            self.descent = cd
            return super()._to_game_model(coords, cd)

    return _Keeping(training_config)


def _handed_over(descent, training_config):
    """Of the coordinate descent's result only what ``reference_check``
    compares, by fixed-effect coordinate: the training scores it ended
    with (the device array, untouched) and its solver's last record.
    The coefficients, the other coordinates' scores and the total go
    with the fit: an outcome kept through the window holds no device
    memory that a deployment would not (the whole result was 0.27 GB of
    ``peak_hbm_gb`` at the wide cell's size: my chip runs, PR 34)."""
    from photon_ml_tpu.config import CoordinateKind

    if descent is None:
        return None
    names = [c.name for c in training_config.coordinates
             if c.kind == CoordinateKind.FIXED_EFFECT]
    return {"scores": {name: descent.scores[name] for name in names},
            "last": {name: dict(descent.history[-1][name])
                     for name in names}}


def one(state):
    """One fit; returns the model, its validation AUC as a float, and
    what the descent hands over of its own record (``_handed_over``)."""
    from photon_ml_tpu.evaluation import EvaluatorType

    estimator = _keeping_estimator(state["training_config"])
    result = estimator.fit(state["train"], state["valid"])[0]
    descent = getattr(result, "descent", None) or estimator.descent
    return {"model": result.model,
            "auc": float(result.evaluations[EvaluatorType.AUC]),
            "descent": _handed_over(descent, state["training_config"])}


def ok(outcome, warm):
    """Whether a fit of the window counts: finite, and the warm-up's
    result again."""
    return (math.isfinite(outcome["auc"])
            and abs(outcome["auc"] - warm["auc"]) <= REPEAT_ATOL)


def not_ok(outcome):
    """Outcomes that ``ok`` must refuse beside ``outcome``."""
    return [dict(outcome, auc=float("nan")),
            dict(outcome, auc=outcome["auc"] + 10 * REPEAT_ATOL)]


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


def _seen(state, scores, n):
    """By coordinate, the sum of the other coordinates' scores as its
    solver saw them.  In a one-sweep fit that state is known from the
    exported model: the coordinates trained before it at their final
    coefficients, those after it at zero.  With more sweeps only the
    last coordinate's is."""
    fields = state["config"]["training_config"]
    order = fields["update_sequence"]
    one_sweep = fields["n_iterations"] == 1
    out = {}
    for name in (order if one_sweep else order[-1:]):
        seen = (order[:order.index(name)] if one_sweep
                else [other for other in order if other != name])
        out[name] = sum((scores[other] for other in seen), np.zeros(n))
    return out


def _fixed_effect_readings(outcome, blocks, scores, ends):
    """What the fit itself computed through the fixed effect's own
    plans, against the plain float64 reference at the exported
    coefficients, worst over the fixed-effect coordinates:

    ``scores``: the training scores the descent held when it ended (the
    coordinate's own ``score``: every column class of its layout)
    against ``plain.margins``, the largest row error relative to
    ``max(1, |score|)``: the forward contraction;
    ``value`` and ``gradient_norm``: the solver's own last objective
    value and gradient norm (the descent's ``history``) against the
    plain value and gradient norm at the state that solver saw: the
    transposed contraction, and the forward one along the line
    search's margins.  The value's distance is relative to the value
    (read, and limited by no configuration: ``FIXED_EFFECT_READINGS``);
    the norm's to the gradient's norm at zero coefficients, not to
    itself: an error of X^T r does not shrink with what the solve
    leaves of the gradient (as a share of the norm itself the float32
    fits read 4.8e-6 to 5.6e-5, the higher the further the solve got;
    as a share of the norm at zero under 1.6e-6 whether the solve made
    2, 15 or 30 iterations: my chip runs, PR 34).  ``ends`` is
    ``plain.coordinate_end`` by coordinate; the two are left out for a
    coordinate that is not in it (its solver's state is not known:
    ``_seen``).

    None where the descent was not handed over."""
    descent = outcome.get("descent")
    if descent is None:
        return None
    out = {}
    for name, block in blocks.items():
        if block[3].ndim != 1:
            continue
        held = np.asarray(descent["scores"][name], np.float64)
        error = np.abs(held - scores[name]) / np.maximum(
            1.0, np.abs(scores[name]))
        found = {"scores": float(error.max())}
        last = descent["last"][name]
        if name in ends and "value" in last:
            value, norm, norm_at_zero = ends[name]
            found["value"] = abs(last["value"] - value) / abs(value)
            found["gradient_norm"] = (abs(last["grad_norm"] - norm)
                                      / norm_at_zero)
        for key, reading in found.items():
            out[key] = max(reading, out.get(key, 0.0))
    return out


def reference_check(state, outcome):
    """``correct`` and what it rests on, from the plain reference."""
    config = state["config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    blocks = _blocks(outcome["model"], state, train)
    scores = _scores(blocks)
    ends = {name: plain.coordinate_end(blocks[name], scores[name], others,
                                       train.labels)
            for name, others in _seen(state, scores,
                                      len(train.labels)).items()}
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
        gradients={name: end[1:] for name, end in ends.items()},
        fixed_effect=_fixed_effect_readings(outcome, blocks, scores, ends),
        reported_auc=outcome["auc"],
        auc_floor=config["auc_floor"],
        objective_gap=config["objective_gap"],
        gradient_rtol=config["gradient_rtol"],
        fixed_effect_rtol=config["fixed_effect_rtol"])
    out["auc_of_true_margins"] = plain.auc(truth["valid_margins"],
                                           valid.labels)
    return out


# -- what the rehearsals and the limits' readings take from here ---------------

def damaged(state, outcome):
    """[(what was done, the outcome with it done, the conditions of
    ``reference_check`` that must then read false)]: results that are
    not the fit's.  The last coordinate's coefficients zeroed: the
    model no longer scores as the program said it did."""
    import jax.numpy as jnp

    model = copy.copy(outcome["model"])
    model.models = dict(model.models)
    name = state["training_config"].coordinates[-1].name
    part = model.models[name]
    if hasattr(part, "coefficient_blocks"):
        zeroed = dataclasses.replace(part, coefficient_blocks=[
            jnp.zeros_like(b) for b in part.coefficient_blocks])
    else:
        zeroed = dataclasses.replace(
            part, coefficients=dataclasses.replace(
                part.coefficients,
                means=jnp.zeros_like(part.coefficients.means)))
    model.models[name] = zeroed
    return [(f"{name} zeroed", dict(outcome, model=model), ["auc_agrees"])]


def _with_iterations(state, iterations_of):
    """``state`` again with every coordinate's ``max_iters`` put through
    ``iterations_of``."""
    config = copy.deepcopy(state["config"])
    for coordinate in config["training_config"]["coordinates"]:
        optimizer = coordinate["optimizer"]
        optimizer["max_iters"] = iterations_of(optimizer["max_iters"])
    return prepare(config, state["traffic"],
                   (state["train"], state["valid"], state["truth"]))


SHORT_ITERATIONS = 2


def cut_short(state, outcome):
    """Left-out work shows.  Returns (``tight``, ``short``, the
    conditions that still hold for the short solve, those of which one
    at least must not): ``tight`` is ``state`` with its limits set just
    above what the whole solve ``outcome`` reaches, ``short`` the same
    with every coordinate's solve stopped after two iterations."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-3
    config["gradient_rtol"] = {name: 2 * value for name, value
                               in whole["gradient_rel"].items()}
    tight = dict(state, config=config)
    return (tight, _with_iterations(tight, lambda _n: SHORT_ITERATIONS),
            ["auc_agrees"], ["objective_reached", "gradient_small"])


@contextlib.contextmanager
def _patched(owner, **methods):
    """``owner``'s methods replaced while the context lasts.  The
    jitted programs are traced with whatever these methods are at the
    time, so the caches are cleared on both sides."""
    import jax

    kept = {name: getattr(owner, name) for name in methods}
    jax.clear_caches()
    for name, method in methods.items():
        setattr(owner, name, method)
    try:
        yield
    finally:
        for name, method in kept.items():
            setattr(owner, name, method)
        jax.clear_caches()


def _rounded_to_bfloat16(method):
    """``reduce_precision`` and not a pair of casts: XLA may keep the
    excess precision of float32 -> bfloat16 -> float32 (on the v5e it
    did: the training scores of such a fit read 5e-7, PR 34)."""
    import jax

    def rounded(self, v):
        return jax.lax.reduce_precision(method(self, v), exponent_bits=8,
                                        mantissa_bits=7)
    return rounded


@contextlib.contextmanager
def control(name, state):
    """The fits that must NOT be ``correct``, for the limits' upper
    readings (``benchmark/limits.py`` on the chip, the rehearsals at
    their size): yields the state to fit while the context lasts.

    ``bfloat16``: the nearest precision below the configuration's
    float32: every fixed-effect contraction's result rounded to
    bfloat16 in the fitting process (the random effects' dense
    contractions stay float32, so that a fixed-effect condition has to
    catch it).
    ``halved``: every coordinate's iterations halved.
    ``two_iterations``: every solve stopped after two iterations, the
    fault that ``cut_short`` rehearses.
    ``no_tail``: the tail class of the fixed effect's layout left out
    of both contractions."""
    if name == "bfloat16":
        # every contraction of a sparse batch, whatever its layout
        from photon_ml_tpu.data.batch import SparseBatch

        with _patched(SparseBatch, **{
                method: _rounded_to_bfloat16(getattr(SparseBatch, method))
                for method in ("margins", "x_dot", "xt_dot")}):
            yield state
    elif name == "halved":
        yield _with_iterations(state, lambda n: max(1, n // 2))
    elif name == "two_iterations":
        yield _with_iterations(state, lambda _n: SHORT_ITERATIONS)
    elif name == "no_tail":
        import jax.numpy as jnp
        from photon_ml_tpu.data.grr import GrrTail

        with _patched(
                GrrTail,
                dot=lambda self, w: jnp.zeros((self.n_rows,), w.dtype),
                t_dot=lambda self, r: jnp.zeros((self.dim,), r.dtype)):
            yield state
    else:
        raise KeyError(f"no control {name!r}")


CONTROLS = ("bfloat16", "halved", "two_iterations", "no_tail")
