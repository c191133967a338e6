"""The operation ``fit_projected``: ``fit`` (one whole
``GameEstimator(cfg).fit(train, valid)``, ended when the validation AUC
is a Python float) of a model whose random effects lie over sparse
shards and are solved in per-entity subspaces.

What is the same is ``operations/fit.py``'s, loaded by its file: the
mix's switches, what counts as a repeat, ``fit_s``, the iteration
controls.  Its own: the descent is read from ``FitResult.descent`` and
from nowhere else (a program without the field is refused before any
data is made: no private name of the program is used); a random effect
is exported through ``RandomEffectModel.projection.feature_ids``,
``coefficient_blocks`` and the grouping's entity ids and held against
``reference/projected.py``; and ``correct`` has a sixth condition,
``random_effect_exact``: each random effect's own training scores, as
the descent ended with them (computed through the projected dense
blocks), against the plain key join.

The interface an operation gives the harness is in
``benchmark/README.md``.
"""

import contextlib
import copy
import dataclasses
import os

import numpy as np

from benchmark.harness import manifest as manifests
from benchmark.reference import plain, projected

fit = manifests.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fit.py"))

ok, not_ok, summary, end_to_end = (fit.ok, fit.not_ok, fit.summary,
                                   fit.end_to_end)

LIMIT_KEYS = fit.LIMIT_KEYS + ("random_effect_rtol",)
RANDOM_EFFECT_READINGS = ("scores",)
CONDITION = "random_effect_exact"


def refuse_a_fit_that_hands_nothing_over():
    """Stop where the program's ``FitResult`` has no ``descent``: the
    conditions on what the fit itself computed would have nothing to
    read, and this operation takes it from no other place."""
    from photon_ml_tpu.estimators.game_estimator import FitResult

    if "descent" not in {f.name for f in dataclasses.fields(FitResult)}:
        raise RuntimeError(
            "FitResult has no field `descent`: this program's fit hands "
            "nothing of its coordinate descent over, and fit_projected "
            "reads it from nowhere else")


# The fixed effect's gradient norm after 30 Armijo-only iterations lies
# anywhere in two decades (0.0057 to 0.297 over 23 sound seeds of
# glmix-kdd12, one seed alone over 0.05; PERF.md section 2: it
# "separates nothing"), so ``fit``'s cap of 0.5 leaves no room over its
# highest sound reading.  What the limit can tell is a trained fixed
# effect from one returned at its start, which reads 1 by construction:
# this operation holds it under that.
FIXED_GRADIENT_RTOL_MOST = 0.95


def limit_problems(config):
    """``fit.limit_problems`` but for the fixed effect's gradient
    limit, which is held to ``FIXED_GRADIENT_RTOL_MOST``; and
    ``random_effect_rtol``."""
    fixed = [c["name"] for c in config["training_config"]["coordinates"]
             if c["kind"] == "FIXED_EFFECT"]
    limits = config.get("gradient_rtol") or {}
    held = dict(config, gradient_rtol={
        name: (fit.GRADIENT_RTOL_MOST / 2 if name in fixed else value)
        for name, value in limits.items()})
    problems = fit.limit_problems(held if "gradient_rtol" in config
                                  else config)
    if not all(0 < limits.get(name, 0) < FIXED_GRADIENT_RTOL_MOST
               for name in fixed if name in limits):
        problems.append("gradient_rtol: a fixed effect's limit outside "
                        f"(0, {FIXED_GRADIENT_RTOL_MOST})")
    rtol = config.get("random_effect_rtol")
    if not rtol or not config.get("random_effect_rtol_derivation"):
        return problems + ["random_effect_rtol: missing, or without its "
                           "random_effect_rtol_derivation"]
    if set(rtol) != set(RANDOM_EFFECT_READINGS):
        problems.append("random_effect_rtol: limits "
                        f"{RANDOM_EFFECT_READINGS} and nothing else")
    if not all(0 < v < fit.FIXED_EFFECT_RTOL_MOST for v in rtol.values()):
        problems.append("random_effect_rtol: a limit outside (0, 2**-11), "
                        "which a bfloat16 contraction could pass")
    return problems


# A tiny fit is held to the cell's own limit of precision, as the fixed
# effect's are (``fit.rehearsal_config``).
rehearsal_config = fit.rehearsal_config


def prepare(config, traffic, data):
    refuse_a_fit_that_hands_nothing_over()
    return fit.prepare(config, traffic, data)


def _handed_over(descent):
    """Of the coordinate descent's result only what ``reference_check``
    compares: each coordinate's training scores as the descent ended
    with them (the device arrays, untouched) and each solver's last
    record."""
    return {"scores": dict(descent.scores),
            "last": {name: dict(record)
                     for name, record in descent.history[-1].items()}}


def one(state):
    """One fit: the model, its validation AUC as a float, and what the
    descent hands over (``_handed_over``).  ``state["spoil"]``, which
    only a control sets, is applied to the outcome."""
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation import EvaluatorType

    result = GameEstimator(state["training_config"]).fit(
        state["train"], state["valid"])[0]
    outcome = {"model": result.model,
               "auc": float(result.evaluations[EvaluatorType.AUC]),
               "descent": _handed_over(result.descent)}
    spoil = state.get("spoil")
    return spoil(outcome) if spoil else outcome


# -- the exported model beside the rows, as the references take them -----------

def _model_table(part, width):
    """A projected random effect's export as ``projected.table``: every
    (entity id, global column) its subspaces hold, with the
    coefficient of that local column."""
    entity_at = part.grouping.entity_row_map()  # (bucket, slot) -> entity
    keys, coefficients = [], []
    for b, block in enumerate(part.coefficient_blocks):
        ids = part.projection.feature_ids[b]
        entity = np.asarray(part.grouping.entity_ids, np.int64)[
            entity_at[b, :len(ids)]]
        slot, local = np.nonzero(ids >= 0)
        keys.append(entity[slot] * np.int64(width) + ids[slot, local])
        coefficients.append(np.asarray(block, np.float64)[slot, local])
    return projected.table(np.concatenate(keys),
                           np.concatenate(coefficients))


def _exported(model, state):
    """The model's exported coefficients by coordinate name, each with
    its L2 weight: the fixed effect's (w, reg_weight), a random
    effect's (table, reg_weight, width)."""
    from photon_ml_tpu.config import CoordinateKind

    weights = {c["name"]: c["optimizer"]["reg_weight"]
               for c in state["config"]["training_config"]["coordinates"]}
    exported = {}
    for coord in state["training_config"].coordinates:
        part = model.models[coord.name]
        if coord.kind == CoordinateKind.FIXED_EFFECT:
            exported[coord.name] = (
                np.asarray(part.coefficients.means, np.float64),
                weights[coord.name])
        else:
            width = part.projection.global_dim
            exported[coord.name] = (_model_table(part, width),
                                    weights[coord.name], width)
    return exported


def _blocks(exported, state, data):
    """``_exported`` beside ``data``'s rows, by coordinate name: the
    fixed effect as ``plain`` takes it (indptr, cols, vals, w,
    reg_weight), a random effect as ``projected`` does (indptr, cols,
    vals, row entity, table, reg_weight, width)."""
    blocks = {}
    for coord in state["training_config"].coordinates:
        rows = data.features[coord.feature_shard]
        blocks[coord.name] = (rows.indptr, rows.cols, rows.vals) + (
            () if coord.entity_key is None
            else (data.entity_ids[coord.entity_key],)
        ) + exported[coord.name]
    return blocks


def _is_fixed(block):
    return len(block) == 5


def _scores(blocks):
    """By coordinate, the plain scores of its rows."""
    return {name: (plain.margins(block[:4], []) if _is_fixed(block)
                   else projected.margins(*block[:5], block[6]))
            for name, block in blocks.items()}


def _random_effect_readings(outcome, scores, blocks):
    """Each random effect's training scores as the descent held them
    when it ended (the coordinate's own ``score``: its projected dense
    blocks against its local coefficients, gathered back to the rows)
    against the plain key join over global columns, the largest row
    error relative to ``max(1, |score|)``; worst over the random
    effects.  It sees a local column mapped back to the wrong global
    one, and a contraction below float32."""
    held = outcome["descent"]["scores"]
    return {"scores": max(
        projected.largest_error(held[name], scores[name])
        for name, block in blocks.items() if not _is_fixed(block))}


def reference_check(state, outcome):
    """``correct`` and what it rests on: ``fit``'s five conditions with
    the random effects through ``projected``, and
    ``random_effect_exact``."""
    config = state["config"]
    train, valid, truth = state["train"], state["valid"], state["truth"]
    exported = _exported(outcome["model"], state)
    blocks = _blocks(exported, state, train)
    scores = _scores(blocks)
    ends = {name: (plain if _is_fixed(blocks[name]) else projected)
            .coordinate_end(blocks[name], scores[name], others, train.labels)
            for name, others in fit._seen(state, scores,
                                          len(train.labels)).items()}
    fixed = {name: b for name, b in blocks.items() if _is_fixed(b)}
    out = plain.check(
        valid_margins=sum(_scores(_blocks(exported, state,
                                          valid)).values()),
        valid_labels=valid.labels,
        train_margins=sum(scores.values()),
        train_labels=train.labels,
        train_penalty=plain.penalty(*fixed.values(), []) + sum(
            projected.penalty(b[4], b[5]) for b in blocks.values()
            if not _is_fixed(b)),
        true_train_margins=truth["train_margins"],
        gradients={name: end[1:] for name, end in ends.items()},
        fixed_effect=fit._fixed_effect_readings(outcome, fixed, scores,
                                                ends),
        reported_auc=outcome["auc"],
        auc_floor=config["auc_floor"],
        objective_gap=config["objective_gap"],
        gradient_rtol=config["gradient_rtol"],
        fixed_effect_rtol=config["fixed_effect_rtol"])
    found = _random_effect_readings(outcome, scores, blocks)
    for name, limit in config["random_effect_rtol"].items():
        out["compared"]["random_effect." + name] = {
            "value": found[name], "limit": limit}
    out["random_effect_rel"] = found
    out["conditions"][CONDITION] = all(
        np.isfinite(found[name]) and found[name] <= limit
        for name, limit in config["random_effect_rtol"].items())
    out["correct"] = all(out["conditions"].values())
    out["auc_of_true_margins"] = plain.auc(truth["valid_margins"],
                                           valid.labels)
    return out


# -- what the rehearsals and the limits' readings take from here ---------------

def _slopes_zeroed(outcome):
    """``outcome`` with every projected coefficient block zeroed but
    for its constant column (the shard's last global column: each
    entity's intercept): the model a fit without the subspaces would
    have exported, under the AUC and the scores of the fit that had
    them."""
    import jax.numpy as jnp

    model = copy.copy(outcome["model"])
    model.models = dict(model.models)
    for name, part in model.models.items():
        if getattr(part, "projection", None) is None:
            continue
        constant = part.projection.global_dim - 1
        model.models[name] = dataclasses.replace(
            part, coefficient_blocks=[
                jnp.where(jnp.asarray(ids == constant), block, 0.0)
                for ids, block in zip(part.projection.feature_ids,
                                      part.coefficient_blocks)])
    return dict(outcome, model=model)


def damaged(state, outcome):
    """``fit``'s (the last coordinate zeroed), and the slopes zeroed:
    the scores the descent held are then not the exported model's."""
    return fit.damaged(state, outcome) + [
        ("slopes zeroed", _slopes_zeroed(outcome), [CONDITION])]


def cut_short(state, outcome):
    """As ``fit.cut_short``, through this operation's check."""
    whole = reference_check(state, outcome)
    config = copy.deepcopy(state["config"])
    config["objective_gap"] = whole["objective_gap"] + 1e-3
    config["gradient_rtol"] = {name: 2 * value for name, value
                               in whole["gradient_rel"].items()}
    tight = dict(state, config=config)
    return (tight,
            fit._with_iterations(tight, lambda _n: fit.SHORT_ITERATIONS),
            ["auc_agrees"], ["objective_reached", "gradient_small"])


@contextlib.contextmanager
def control(name, state):
    """The fits that must NOT be ``correct``:

    ``bfloat16_re``: the nearest precision below the configuration's
    float32 in every random-effect contraction: the solves' dense
    contractions (``DenseBatch``) and the block-space scoring the
    descent ends with, each result rounded to bfloat16 in the fitting
    process; the fixed effect stays float32, so that a random-effect
    condition has to catch it.
    ``two_iterations``: every solve stopped after two iterations.
    ``slopes_zeroed``: a whole fit, then ``_slopes_zeroed``."""
    if name == "bfloat16_re":
        from photon_ml_tpu.data.batch import DenseBatch
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate

        # ``score`` gathers the blocks' products back to the rows, which
        # moves numbers and changes none: rounding its result rounds
        # every contraction's.
        with fit._patched(DenseBatch, **{
                method: fit._rounded_to_bfloat16(getattr(DenseBatch, method))
                for method in ("margins", "x_dot", "xt_dot")}), \
                fit._patched(RandomEffectCoordinate,
                             score=fit._rounded_to_bfloat16(
                                 RandomEffectCoordinate.score)):
            yield state
    elif name == "two_iterations":
        yield fit._with_iterations(state, lambda _n: fit.SHORT_ITERATIONS)
    elif name == "slopes_zeroed":
        yield dict(state, spoil=_slopes_zeroed)
    else:
        raise KeyError(f"no control {name!r}")


CONTROLS = ("bfloat16_re", "two_iterations", "slopes_zeroed")
