"""The plain reference of a weighted least-squares GAME model: float64
numpy, nothing of photon_ml_tpu, beside ``plain.py`` (whose
per-entity contractions it uses).  The fixed effect's two contractions
are made a block of rows at a time, so that no temporary is larger
than a block's entries.

margin(row) = x_fixed(row) . w       the intercept is w's last entry
            + sum over random effects of x_re(row) . coef[entity(row)]

loss(row)   = 1/2 weight(row) (margin - label)^2

objective   = sum over rows of loss
            + 1/2 lambda_c |coef_c|^2 for every coordinate c, the fixed
              effect's intercept left out (the program's convention).

For a click-through rate the label is clicks / impressions and the
weight the impressions: the loss of a row is then the binomial
least-squares loss of its impressions, one at a time.

A block is ``plain``'s: the fixed effect (indptr, cols, vals, w,
reg_weight), a random effect (x, row entity, sorted entity ids, coefs
[E, p], reg_weight).
"""

import numpy as np

from benchmark.reference import plain

# The program's RMSE is a float32 sum over 1e4 to 2e5 rows; this file's
# is float64.  A term differs by about 1e-7 of itself, the root of the
# mean by less (CPU, 120 rows: 3e-8).  1e-3 of the RMSE is far above
# that and far below what a zeroed coordinate does to it.
RMSE_RTOL = 1e-3
# Rows a block of the fixed effect's contractions.
BLOCK_ROWS = 1 << 20


def _blocks(indptr):
    """(first row, end row, first entry, end entry) of every block."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(n, lo + BLOCK_ROWS)
        yield lo, hi, int(indptr[lo]), int(indptr[hi])


def csr_dot(indptr, cols, vals, w):
    """[n] row sums of vals * w[cols], float64, a block at a time."""
    indptr = np.asarray(indptr, np.int64)
    w = np.asarray(w, np.float64)
    out = np.zeros(len(indptr) - 1)
    for lo, hi, a, b in _blocks(indptr):
        terms = w[cols[a:b]] * np.asarray(vals[a:b], np.float64)
        rows = np.repeat(np.arange(hi - lo), np.diff(indptr[lo:hi + 1]))
        out[lo:hi] = np.bincount(rows, weights=terms, minlength=hi - lo)
    return out


def csr_t_dot(indptr, cols, vals, r, d):
    """[d] column sums of vals * r[row] (X^T r), float64, a block at a
    time."""
    indptr = np.asarray(indptr, np.int64)
    r = np.asarray(r, np.float64)
    out = np.zeros(d)
    for lo, hi, a, b in _blocks(indptr):
        rows = np.repeat(np.arange(lo, hi), np.diff(indptr[lo:hi + 1]))
        out += np.bincount(cols[a:b], minlength=d,
                           weights=r[rows] * np.asarray(vals[a:b],
                                                        np.float64))[:d]
    return out


def fixed_scores(block):
    """[n] the fixed effect's scores, intercept in."""
    indptr, cols, vals, w, _reg_weight = block
    w = np.asarray(w, np.float64)
    return csr_dot(indptr, cols, vals, w[:-1]) + w[-1]


def weighted_rmse(predictions, labels, weights):
    """The root of the weighted mean squared error."""
    e = np.asarray(predictions, np.float64) - np.asarray(labels, np.float64)
    weights = np.asarray(weights, np.float64)
    return float(np.sqrt(np.sum(weights * e * e) / np.sum(weights)))


def weighted_loss(margins, labels, weights):
    """sum of 1/2 weight (margin - label)^2."""
    e = np.asarray(margins, np.float64) - np.asarray(labels, np.float64)
    return 0.5 * float(np.sum(np.asarray(weights, np.float64) * e * e))


def penalty(fixed, random_effects):
    """The objective's L2 term, the fixed effect's intercept left out."""
    total = 0.5 * fixed[4] * float(np.sum(
        np.asarray(fixed[3], np.float64)[:-1] ** 2))
    for *_, coefs, lam in random_effects:
        total += 0.5 * lam * float(np.sum(np.asarray(coefs, np.float64) ** 2))
    return total


def fixed_effect_gradient(block, margins, labels, weights):
    """[d + 1] the gradient of the fixed effect's objective at every
    row's margin: X^T (weight (m - y)) + lambda w, the intercept's entry
    last and without the L2 term."""
    indptr, cols, vals, w, lam = block
    w = np.asarray(w, np.float64)
    r = np.asarray(weights, np.float64) * (
        np.asarray(margins, np.float64) - np.asarray(labels, np.float64))
    g = np.empty(len(w))
    g[:-1] = csr_t_dot(indptr, cols, vals, r, len(w) - 1) + lam * w[:-1]
    g[-1] = np.sum(r)
    return g


def fixed_effect_end(block, own_scores, seen, labels, weights):
    """(the objective the fixed effect's solver minimises at its
    coefficients, the norm of its gradient there, the same norm at zero
    coefficients); ``seen`` is what its solver saw beside its own scores
    (the scores of the coordinates trained before it)."""
    w = np.asarray(block[3], np.float64)
    z = seen + own_scores
    value = (weighted_loss(z, labels, weights)
             + 0.5 * block[4] * float(np.sum(w[:-1] ** 2)))
    g = fixed_effect_gradient(block, z, labels, weights)
    g0 = fixed_effect_gradient(block[:3] + (np.zeros_like(w), block[4]),
                               seen, labels, weights)
    return value, float(np.linalg.norm(g)), float(np.linalg.norm(g0))


def random_effect_end(block, own_scores, seen, labels, weights):
    """(norm of the gradient of a random effect's objective at its
    coefficients, the same at zero coefficients): the weighted squared
    loss of its rows plus 1/2 lambda |coefs|^2."""
    x, row_ids, entity_ids, coefs, lam = block
    coefs = np.asarray(coefs, np.float64)
    labels = np.asarray(labels, np.float64)
    weights = np.asarray(weights, np.float64)
    g = plain.entity_t_dot(x, row_ids, entity_ids,
                           weights * (seen + own_scores - labels)) \
        + lam * coefs
    g0 = plain.entity_t_dot(x, row_ids, entity_ids, weights * (seen - labels))
    return float(np.linalg.norm(g)), float(np.linalg.norm(g0))


def rmse_of_the_pooled_rate(train_labels, train_weights, labels, weights):
    """The validation RMSE of the one-number model, rate = all training
    clicks / all training impressions: what a fit has to beat to have
    learnt anything."""
    train_weights = np.asarray(train_weights, np.float64)
    rate = float(np.sum(train_weights * np.asarray(train_labels, np.float64))
                 / np.sum(train_weights))
    return weighted_rmse(np.full(len(labels), rate), labels, weights)


def check(*, valid_margins, valid_labels, valid_weights, reported_rmse,
          train_margins, train_labels, train_weights, train_penalty,
          true_train_margins, gradients, fixed_effect, baseline_rmse,
          limits):
    """Five conditions, all needed for ``correct``:

    (a) ``rmse_agrees``: the plain weighted RMSE of the exported
        coefficients on the validation rows is the program's reported
        ``RMSE`` within ``RMSE_RTOL`` of itself;
    (b) ``objective_reached``: the objective per training impression
        (weight) at the exported coefficients is at most the weighted
        squared loss of the generating rates on the same rows, per
        impression, plus ``objective_gap``;
    (c) ``optimal``: ``gradients`` gives, by coordinate, (the norm of
        its objective's gradient at the state its solver saw, the same
        at zero coefficients), weights in on both; the first is at most
        the coordinate's ``gradient_rtol`` of the second;
    (d) ``beats_baseline``: the validation RMSE is under
        ``baseline_rmse`` (for a cell the one-number model's on the same
        rows, ``rmse_of_the_pooled_rate``) by more than
        ``rmse_gain_floor``;
    (e) ``fixed_effect_exact``: ``fixed_effect`` gives what the fit
        itself computed through its own plans (its training scores, its
        solver's last gradient norm) as relative distances from the
        plain numbers; each that ``fixed_effect_rtol`` names is at most
        its limit.

    ``limits`` holds ``objective_gap``, ``gradient_rtol`` (by
    coordinate), ``rmse_gain_floor`` and ``fixed_effect_rtol`` (by
    reading)."""
    plain_rmse = weighted_rmse(valid_margins, valid_labels, valid_weights)
    impressions = float(np.sum(np.asarray(train_weights, np.float64)))
    objective = (weighted_loss(train_margins, train_labels, train_weights)
                 + train_penalty) / impressions
    true_loss = weighted_loss(true_train_margins, train_labels,
                              train_weights) / impressions
    relative = {name: found / at_zero
                for name, (found, at_zero) in gradients.items()}
    fixed_effect = fixed_effect or {}
    compared = {
        "rmse_difference": {
            "value": abs(plain_rmse - reported_rmse) / plain_rmse,
            "limit": RMSE_RTOL},
        "objective_gap": {"value": objective - true_loss,
                          "limit": limits["objective_gap"]},
        "rmse_gain": {"value": baseline_rmse - plain_rmse,
                      "at_least": limits["rmse_gain_floor"]},
    }
    for name, value in relative.items():
        compared["gradient." + name] = {
            "value": value, "limit": limits["gradient_rtol"][name]}
    for name, limit in limits["fixed_effect_rtol"].items():
        compared["fixed_effect." + name] = {
            "value": fixed_effect.get(name), "limit": limit}

    def holds(*names):
        def one(value, limit=None, at_least=None):
            if value is None or not np.isfinite(value):
                return False
            return value <= limit if at_least is None else value > at_least
        return all(one(**compared[name]) for name in names)

    conditions = {
        "rmse_agrees": holds("rmse_difference"),
        "objective_reached": holds("objective_gap"),
        "optimal": holds(*("gradient." + name for name in relative)),
        "beats_baseline": holds("rmse_gain"),
        "fixed_effect_exact": holds(
            *("fixed_effect." + name
              for name in limits["fixed_effect_rtol"])),
    }
    return {
        "plain_rmse": plain_rmse, "reported_rmse": float(reported_rmse),
        "objective_per_impression": objective,
        "true_rate_loss": true_loss, "objective_gap": objective - true_loss,
        "baseline_rmse": baseline_rmse, "gradient_rel": relative,
        "fixed_effect_rel": fixed_effect, "conditions": conditions,
        "correct": all(conditions.values()), "compared": compared,
    }
