"""The operation ``fit_refresh``: GLMix's daily refresh.  In set-up, day
N's job: the configuration's coordinates fitted cold on day N's rows
(nothing locked, no prior), its random effects' SIMPLE variances
computed, the model exported to a directory of this run (its one-file
manifest).  Each timed
operation is day N+1's job: one whole ``GameEstimator(cfg).fit(train,
valid)`` with ``warm_start_model_dir`` at that directory (reading the
model is inside the fit, as in a real job), the coordinates the
configuration locks held at day N's, every other random effect
retrained toward day N's posterior (``use_warm_start_as_prior``: each
entity's prior is its mean and SIMPLE variance of yesterday), its
variances exported again; ended when the validation AUC is a Python
float.

What is the same is ``operations/fit.py``'s, loaded by its file: the
mix's switches, what counts as a repeat, ``fit_s``.  From
``operations/fit_projected.py``: the descent handed over by
``FitResult.descent``, a projected model's export as a key table, and
the bfloat16 control.  The check, against ``reference/refresh.py``,
has six conditions (``benchmark/README.fit_refresh.md``):

(a) ``auc_agrees``: the plain validation AUC of the exported model is
    the program's within ``plain.AUC_ATOL``;
(b) ``global_locked``: every locked coordinate's export is day N's, bit
    for bit;
(c) ``optimal_with_prior``: each random effect's gradient of f_e (the
    prior's term in it), at the other coordinates' scores as its solver
    saw them, as a share of the same at its warm start, is under its
    ``gradient_rtol`` (the random effects it names; the others' are
    read and held to nothing, ``unheld``);
(d) ``random_effect_exact``: as ``fit_projected``'s (f), each random
    effect's training scores as the descent ended with them against
    the plain key join, under ``random_effect_rtol``;
(e) ``variances_exact``: the exported SIMPLE variances against the
    plain 1 / diag of f_e's Hessian (the prior's precision included) at
    the exported coefficients and the other coordinates' final scores,
    the largest relative error, under ``variance_rtol``; and day N's
    exported variances, the prior of today's solves and of the
    reference, against the same at day N's coefficients and scores
    (no prior), under the same limit;
(f) ``beats_yesterday``: today's plain validation AUC over that of day
    N's model as it was exported, by ``auc_gain_floor``.

One descent sweep only: the check knows the state each solver saw from
the exported models (the coordinates before it at today's
coefficients, those after it at their warm starts).

The interface an operation gives the harness is in
``benchmark/README.md``.
"""

import atexit
import contextlib
import copy
import dataclasses
import os
import shutil
import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import manifest as manifests
from benchmark.reference import plain, projected, refresh

HERE = os.path.dirname(os.path.abspath(__file__))
fit = manifests.load_module(os.path.join(HERE, "fit.py"))
fit_projected = manifests.load_module(os.path.join(HERE,
                                                   "fit_projected.py"))

ok, not_ok, summary, end_to_end = (fit.ok, fit.not_ok, fit.summary,
                                   fit.end_to_end)

LIMIT_KEYS = ("gradient_rtol", "random_effect_rtol", "variance_rtol",
              "auc_gain_floor")
# Each run's day-N models, removed when the process ends.
MODEL_ROOT = os.path.join(manifests.BENCH_DIR, "out", "models")


def refuse_a_program_without_entity_priors():
    """Stop where the program's random effects take no prior: its
    ``use_warm_start_as_prior`` would retrain them toward zero, and the
    cell would time another problem than the one it names."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate

    if "prior" not in {f.name for f in
                       dataclasses.fields(RandomEffectCoordinate)}:
        raise RuntimeError(
            "RandomEffectCoordinate has no field `prior`: this program's "
            "random effects take no prior from a warm-start model, and "
            "fit_refresh times a refresh whose random effects do")


def _random_names(config):
    return [c["name"] for c in config["training_config"]["coordinates"]
            if c["kind"] == "RANDOM_EFFECT"]


def limit_problems(config):
    """What is wrong with the limits a configuration states for this
    operation, as a list of sentences; empty when nothing is."""
    problems = [f"{key}: missing, or without its {key}_derivation"
                for key in LIMIT_KEYS
                if key not in config or not config.get(key + "_derivation")]
    if problems:
        return problems
    fields = config["training_config"]
    if fields.get("n_iterations") != 1 or not fields.get(
            "use_warm_start_as_prior"):
        problems.append("training_config: one descent sweep, with "
                        "use_warm_start_as_prior, is what the check reads")
    fixed = {c["name"] for c in fields["coordinates"]
             if c["kind"] == "FIXED_EFFECT"}
    if not set(fields.get("locked_coordinates", ())) <= fixed:
        problems.append("training_config: a locked coordinate that is not "
                        "a fixed effect, whose export the check compares")
    limits = config["gradient_rtol"]
    if not limits or not set(limits) <= set(_random_names(config)):
        problems.append("gradient_rtol: not a limit for one random effect "
                        "or more, and for nothing else")
    if not all(0 < v < fit.GRADIENT_RTOL_MOST for v in limits.values()):
        problems.append("gradient_rtol: a limit outside "
                        f"(0, {fit.GRADIENT_RTOL_MOST})")
    rtol = config["random_effect_rtol"]
    if set(rtol) != set(fit_projected.RANDOM_EFFECT_READINGS):
        problems.append("random_effect_rtol: limits "
                        f"{fit_projected.RANDOM_EFFECT_READINGS} and nothing "
                        "else")
    for name, value in [("random_effect_rtol", v) for v in rtol.values()] \
            + [("variance_rtol", config["variance_rtol"])]:
        if not 0 < value < fit.FIXED_EFFECT_RTOL_MOST:
            problems.append(f"{name}: a limit outside (0, 2**-11), which a "
                            "bfloat16 contraction could pass")
    if not 0 <= config["auc_gain_floor"] < 0.1:
        problems.append("auc_gain_floor: not in [0, 0.1)")
    return problems


# The gradient limit of a rehearsal's held random effects: per_ad's
# whole tiny solves read 0.003 and two iterations of them 0.40.
REHEARSAL_GRADIENT_RTOL = 0.1


def rehearsal_config(config):
    """``config`` at its ``rehearsal_params``.  Two tiny days learn
    little from each other: the solves are held to
    ``REHEARSAL_GRADIENT_RTOL`` and the gain over yesterday only has to
    be a gain of no worse than a coin's; the limits of precision stay
    the cell's."""
    config = copy.deepcopy(config)
    config["generator"]["params"].update(config["rehearsal_params"])
    config["gradient_rtol"] = {name: REHEARSAL_GRADIENT_RTOL
                               for name in config["gradient_rtol"]}
    config["auc_gain_floor"] = -0.5
    return config


def _model_dir():
    os.makedirs(MODEL_ROOT, exist_ok=True)
    path = tempfile.mkdtemp(prefix="yesterday-", dir=MODEL_ROOT)
    atexit.register(shutil.rmtree, path, True)
    return path


def _tables(model, state):
    """By coordinate, the model's export as the reference takes it: a
    fixed effect's means as exported (float32); a random effect's
    ``(keys, means, variances)`` over (entity, global column) pairs,
    sorted by key (variances None where it has none), and its width."""
    from photon_ml_tpu.config import CoordinateKind

    out = {}
    for coord in state["training_config"].coordinates:
        part = model.models[coord.name]
        if coord.kind == CoordinateKind.FIXED_EFFECT:
            out[coord.name] = np.array(part.coefficients.means)
            continue
        width = part.projection.global_dim
        entity_at = part.grouping.entity_row_map()  # (bucket, slot)
        planes = [part.coefficient_blocks] + (
            [] if part.variance_blocks is None else [part.variance_blocks])
        keys, values = [], [[] for _plane in planes]
        for b, ids in enumerate(part.projection.feature_ids):
            entity = np.asarray(part.grouping.entity_ids, np.int64)[
                entity_at[b, :len(ids)]]
            slot, local = np.nonzero(ids >= 0)
            keys.append(entity[slot] * np.int64(width) + ids[slot, local])
            for plane, blocks in zip(values, planes):
                plane.append(np.asarray(blocks[b], np.float64)[slot, local])
        keys = np.concatenate(keys)
        order = np.argsort(keys)  # unique keys: no tie to keep in order
        means, *variances = [np.concatenate(plane)[order]
                             for plane in values]
        out[coord.name] = ((keys[order], means,
                            variances[0] if variances else None), width)
    return out


def _fit_yesterday(config, traffic, rows):
    """Day N's job on ``rows``: the configuration's coordinates, none
    locked and no prior, fitted cold; its model exported to a directory
    of this run.  Returns that directory, the model's ``_tables`` and
    ``rows`` (for the check of its variances)."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.io.model_io import save_model_manifest

    fields = dict(config["training_config"], locked_coordinates=[],
                  use_warm_start_as_prior=False)
    state = fit.prepare(dict(config, training_config=fields), traffic,
                        (rows, None, {}))
    model = GameEstimator(state["training_config"]).fit(rows)[0].model
    model_dir = _model_dir()
    # the one-file export a warm start (and a server) reads
    save_model_manifest(model, state["training_config"].task_type, model_dir)
    return {"model_dir": model_dir, "tables": _tables(model, state),
            "train": rows}


def _today(config, traffic, data, yesterday):
    """``fit.prepare``'s state of day N+1's job, warm-started from
    ``yesterday``'s model."""
    today = copy.deepcopy(config)
    today["training_config"]["warm_start_model_dir"] = yesterday["model_dir"]
    return dict(fit.prepare(today, traffic, data), yesterday=yesterday)


def prepare(config, traffic, data):
    """Day N's job (set-up), then everything ``one`` needs for day
    N+1's."""
    refuse_a_program_without_entity_priors()
    train, valid, truth = data
    truth = dict(truth)
    yesterday = _fit_yesterday(config, traffic, truth.pop("yesterday_train"))
    return _today(config, traffic, (train, valid, truth), yesterday)


def one(state):
    """Day N+1's job: the model, its validation AUC as a float, and what
    the descent hands over."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    result = GameEstimator(state["training_config"]).fit(
        state["train"], state["valid"])[0]
    return {"model": result.model,
            "auc": float(result.evaluations[EvaluatorType.AUC]),
            "descent": fit_projected._handed_over(result.descent)}


# -- the check ------------------------------------------------------------------

def _margins(state, tables, data, rows=None):
    """By coordinate, the plain scores of ``data``'s rows under
    ``tables`` (``rows``: the random effects' ``refresh.Rows`` of
    ``data``, where they are built already)."""
    from photon_ml_tpu.config import CoordinateKind

    out = {}
    for coord in state["training_config"].coordinates:
        shard = data.features[coord.feature_shard]
        if coord.kind == CoordinateKind.FIXED_EFFECT:
            out[coord.name] = plain.margins(
                (shard.indptr, shard.cols, shard.vals, tables[coord.name]),
                [])
            continue
        (keys, means, _variances), width = tables[coord.name]
        if rows is not None:
            out[coord.name] = rows[coord.name].margins(
                refresh.at_keys((keys, means), rows[coord.name].pairs))
        else:
            out[coord.name] = projected.margins(
                shard.indptr, shard.cols, shard.vals,
                data.entity_ids[coord.entity_key], (keys, means), width)
    return out


def _holds(entry):
    value = entry["value"]
    if value is None or not np.isfinite(value):
        return False
    if "at_least" in entry:
        return bool(value > entry["at_least"])
    return bool(value <= entry["limit"])


def _rows(data, coord, width):
    shard = data.features[coord.feature_shard]
    return refresh.Rows(shard.indptr, shard.cols, shard.vals,
                        data.entity_ids[coord.entity_key], width)


def _yesterday_variance_error(state, random, weights, rows):
    """The largest relative error of day N's exported variances against
    the plain 1 / diag of its f_e's Hessian (no prior: day N had none)
    at day N's exported coefficients and the other coordinates' final
    scores on day N's rows (``rows``: the random effects'
    ``refresh.Rows`` of them); None where a random effect exported
    none."""
    before, train = state["yesterday"]["tables"], state["yesterday"]["train"]
    final = _margins(state, before, train, rows)
    total = sum(final.values())
    errors = []
    for c in random:
        keys, means, variances = before[c.name][0]
        if variances is None:
            return None
        pairs = rows[c.name].pairs
        none = (np.zeros(len(pairs)), np.zeros(len(pairs)))
        want = refresh.variances(
            rows[c.name], refresh.at_keys((keys, means), pairs),
            total - final[c.name], train.labels, weights[c.name], none, 0.0)
        errors.append(refresh.largest_relative_error(
            refresh.at_keys((keys, variances), pairs), want))
    return max(errors)


def reference_check(state, outcome):
    """``correct`` and what it rests on: the six conditions of the
    module's docstring, from ``reference/refresh.py``."""
    from photon_ml_tpu.config import CoordinateKind

    config = state["config"]
    fields = config["training_config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    coords = state["training_config"].coordinates
    order = fields["update_sequence"]
    locked = set(fields.get("locked_coordinates", ()))
    weights = {c["name"]: c["optimizer"]["reg_weight"]
               for c in fields["coordinates"]}
    prior_weight = fields["prior_weight"]
    yesterday, model = state["yesterday"], outcome["model"]
    before = yesterday["tables"]
    random = [c for c in coords if c.kind == CoordinateKind.RANDOM_EFFECT]
    # The two days' checks are independent numpy work, most of it sorts
    # that release the GIL: both days' key tables are built side by
    # side, and day N's variances are checked beside today's work.
    pool = ThreadPoolExecutor(2 * len(random) + 2)
    day_n = {c.name: pool.submit(_rows, yesterday["train"], c,
                                 before[c.name][1]) for c in random}
    yesterday_error = pool.submit(lambda: _yesterday_variance_error(
        state, random, weights, {name: f.result()
                                 for name, f in day_n.items()}))
    tables = pool.submit(_tables, model, state)
    rows = {c.name: pool.submit(_rows, train, c,
                                model.models[c.name].projection.global_dim)
            for c in random}
    today = tables.result()
    rows = {name: f.result() for name, f in rows.items()}
    final = _margins(state, today, train, rows)
    priors = {c.name: refresh.prior_at(before[c.name][0], rows[c.name].pairs)
              for c in random}
    # a random effect starts at yesterday's means (its prior's); a
    # locked coordinate is today what it was
    start = dict(final, **{c.name: rows[c.name].margins(priors[c.name][0])
                           for c in random if c.name not in locked})
    total = sum(final.values())
    compared, found, unheld = {}, {}, {}
    for c in random:
        (table, _width) = today[c.name]
        at = refresh.at_keys(table[:2], rows[c.name].pairs)
        prior = priors[c.name]
        i = order.index(c.name)
        seen = sum((final[o] for o in order[:i]), np.zeros(len(total))) \
            + sum((start[o] for o in order[i + 1:]), np.zeros(len(total)))
        _value, norm, at_start = refresh.coordinate_end(
            rows[c.name], at, seen, train.labels, weights[c.name], prior,
            prior_weight)
        if c.name in config["gradient_rtol"]:
            compared["gradient." + c.name] = {
                "value": norm / at_start,
                "limit": config["gradient_rtol"][c.name]}
        else:
            unheld["gradient." + c.name] = norm / at_start
        want = refresh.variances(
            rows[c.name], at, total - final[c.name], train.labels,
            weights[c.name], prior, prior_weight)
        found[c.name] = (None if table[2] is None else
                         refresh.largest_relative_error(refresh.at_keys(
                             (table[0], table[2]), rows[c.name].pairs),
                             want))
    held = outcome["descent"]["scores"]
    compared["random_effect.scores"] = {
        "value": max(projected.largest_error(held[c.name], final[c.name])
                     for c in random),
        "limit": config["random_effect_rtol"]["scores"]}
    compared["variance_error"] = {
        "value": (None if None in found.values() else max(found.values())),
        "limit": config["variance_rtol"]}
    compared["variance_error.yesterday"] = {
        "value": yesterday_error.result(), "limit": config["variance_rtol"]}
    pool.shutdown()
    compared["global_changed"] = {
        "value": sum(int(np.count_nonzero(
            today[name].view(np.uint32) != before[name].view(np.uint32)))
            for name in locked),
        "limit": 0}
    plain_auc = plain.auc(sum(_margins(state, today, valid).values()),
                          valid.labels)
    yesterday_auc = plain.auc(sum(_margins(state, before, valid).values()),
                              valid.labels)
    compared["auc_difference"] = {"value": abs(plain_auc - outcome["auc"]),
                                  "limit": plain.AUC_ATOL}
    compared["auc_gain"] = {"value": plain_auc - yesterday_auc,
                            "at_least": config["auc_gain_floor"]}
    conditions = {
        "auc_agrees": _holds(compared["auc_difference"]),
        "global_locked": _holds(compared["global_changed"]),
        "optimal_with_prior": all(_holds(compared["gradient." + name])
                                  for name in config["gradient_rtol"]),
        "random_effect_exact": _holds(compared["random_effect.scores"]),
        "variances_exact": (_holds(compared["variance_error"])
                            and _holds(compared["variance_error.yesterday"])),
        "beats_yesterday": _holds(compared["auc_gain"]),
    }
    return {"plain_auc": plain_auc, "reported_auc": outcome["auc"],
            "yesterday_auc": yesterday_auc,
            "auc_of_true_margins": plain.auc(truth["valid_margins"],
                                             valid.labels),
            "unheld": unheld, "conditions": conditions,
            "correct": all(conditions.values()), "compared": compared}


# -- what the rehearsals and the limits' readings take from here ---------------

def _global_moved(outcome):
    """The first locked-looking fixed effect's first coefficient one
    float32 step away."""
    import jax.numpy as jnp

    model = copy.copy(outcome["model"])
    model.models = dict(model.models)
    name = next(n for n, part in model.models.items()
                if hasattr(part, "coefficients"))
    part = model.models[name]
    means = np.array(part.coefficients.means)
    means[0] = np.nextafter(means[0], np.float32(np.inf))
    model.models[name] = dataclasses.replace(
        part, coefficients=dataclasses.replace(
            part.coefficients, means=jnp.asarray(means)))
    return dict(outcome, model=model)


def _variances_halved(outcome):
    model = copy.copy(outcome["model"])
    model.models = {name: (dataclasses.replace(part, variance_blocks=[
        v / 2 for v in part.variance_blocks])
        if getattr(part, "variance_blocks", None) is not None else part)
        for name, part in model.models.items()}
    return dict(outcome, model=model)


def damaged(state, outcome):
    """Results that are not the fit's: the last coordinate zeroed
    (``fit``'s), the locked global moved by one float32 step, every
    exported variance halved."""
    return fit.damaged(state, outcome) + [
        ("global moved", _global_moved(outcome), ["global_locked"]),
        ("variances halved", _variances_halved(outcome),
         ["variances_exact"])]


def _with_iterations(state, iterations_of):
    """``state`` again, day N as it was, with every coordinate's
    ``max_iters`` put through ``iterations_of``."""
    config = copy.deepcopy(state["config"])
    for coordinate in config["training_config"]["coordinates"]:
        optimizer = coordinate["optimizer"]
        optimizer["max_iters"] = iterations_of(optimizer["max_iters"])
    return _today(config, state["traffic"],
                  (state["train"], state["valid"], state["truth"]),
                  state["yesterday"])


def cut_short(state, outcome):
    """As ``fit.cut_short``: the gradient limits set just above what the
    whole solve reaches, the same day N+1 solved in two iterations."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["gradient_rtol"] = {
        name.split(".", 1)[1]: 2 * entry["value"]
        for name, entry in whole["compared"].items()
        if name.startswith("gradient.")}
    tight = dict(state, config=config)
    return (tight,
            _with_iterations(tight, lambda _n: fit.SHORT_ITERATIONS),
            ["auc_agrees", "global_locked", "random_effect_exact"],
            ["optimal_with_prior"])


@contextlib.contextmanager
def control(name, state):
    """The fits that must NOT be ``correct``:

    ``prior_dropped``: the random effects get no prior (retrained from
    yesterday's means toward zero, as a program that ignores the prior
    for them would), by (c);
    ``bfloat16_re``: ``fit_projected``'s, every random-effect
    contraction's result rounded to bfloat16, by (d);
    ``variances_without_prior``: the variances exported without the
    prior's precision, by (e);
    ``two_iterations``: every solve stopped after two iterations;
    ``half_rows``: the fit handed day N+1's training rows with every
    other row's weight 0, so that each random effect is solved over
    about half of its entity's rows (the locked global scores every row
    as before), by (c)."""
    if name == "prior_dropped":
        from photon_ml_tpu.estimators.game_estimator import GameEstimator

        with fit._patched(GameEstimator,
                          _random_prior=lambda *_args: (None, 0)):
            yield state
    elif name == "bfloat16_re":
        with fit_projected.control(name, state) as state:
            yield state
    elif name == "variances_without_prior":
        from photon_ml_tpu.game import coordinates

        def without(self, coefficient_blocks, offsets):
            return coordinates._re_variances(
                self.problem.objective, self._blocks(), coefficient_blocks,
                offsets)

        with fit._patched(coordinates.RandomEffectCoordinate,
                          compute_variance_blocks=without):
            yield state
    elif name == "two_iterations":
        yield _with_iterations(state, lambda _n: fit.SHORT_ITERATIONS)
    elif name == "half_rows":
        from photon_ml_tpu.estimators.game_estimator import GameEstimator

        whole = GameEstimator.fit

        def on_half(self, train, *args, **kwargs):
            weights = train.weight_array().copy()
            weights[1::2] = 0.0
            return whole(self, dataclasses.replace(train, weights=weights),
                         *args, **kwargs)

        with fit._patched(GameEstimator, fit=on_half):
            yield state
    else:
        raise KeyError(f"no control {name!r}")


CONTROLS = ("prior_dropped", "bfloat16_re", "variances_without_prior",
            "two_iterations", "half_rows")
