"""The plain reference of a Poisson GAME model with exposures and an
elastic-net fixed effect: float64 numpy, nothing of photon_ml_tpu,
beside ``plain.py`` (whose CSR and per-entity contractions it uses).

margin(row) = offset(row)            log of the row's exposure
            + x_fixed(row) . w       the intercept is w's last entry
            + sum over random effects of x_re(row) . coef[entity(row)]

loss(row)   = exp(margin) - label * margin     the Poisson negative
              log-likelihood of a count, less the term in the label alone

objective   = sum over rows of loss
            + 1/2 l2 |w|^2 + l1 |w|_1 for the fixed effect, with
              l1 = alpha * lambda and l2 = (1 - alpha) * lambda (upstream's
              ELASTIC_NET) and the intercept unpenalised
            + 1/2 lambda_c |coef_c|^2 for every random effect c.

A block is ``plain``'s: the fixed effect (indptr, cols, vals, w,
reg_weight), a random effect (x, row entity, sorted entity ids, coefs
[E, p], reg_weight).
"""

import numpy as np

from benchmark.reference import plain

# The program's validation loss is a float32 mean over 1e4 to 2e5 rows
# of float32 margins; this file's is float64.  A term exp(z) - y z
# differs by about 1e-6 of itself, the mean by less (CPU, 120 rows:
# 2e-8).  1e-3 of the loss is far above that and far below what a
# dropped exposure does: the log of an exposure of 2 is 0.69 of margin.
LOSS_RTOL = 1e-3


def split_weights(reg_weight, alpha):
    """(l1, l2) of upstream's elastic net."""
    return alpha * reg_weight, (1.0 - alpha) * reg_weight


def mean_poisson_loss(margins, labels):
    """Mean of exp(margin) - label * margin."""
    z = np.asarray(margins, np.float64)
    return float(np.mean(np.exp(z) - np.asarray(labels, np.float64) * z))


def loss_of_the_pooled_rate(train_labels, train_exposure, labels, exposure):
    """The mean loss of ``labels`` under the one-number model, rate =
    all training clicks / all training impressions: what a fit has to
    beat to have learnt anything."""
    rate = float(np.sum(train_labels)) / float(np.sum(train_exposure))
    return mean_poisson_loss(np.log(rate * np.asarray(exposure, np.float64)),
                             labels)


def kkt_residual(smooth_gradient, w, l1):
    """The optimality residual of ``smooth + l1 |w|_1`` at ``w``, by
    coordinate: ``g + l1 sign(w)`` where ``w != 0`` and the
    soft-thresholded ``g`` (zero inside ``[-l1, l1]``) where ``w == 0``.
    ``smooth_gradient`` includes the L2 term; ``l1`` is a scalar or one
    weight a coordinate (0 for an unpenalised one, whose residual is
    its gradient)."""
    g = np.asarray(smooth_gradient, np.float64)
    w = np.asarray(w, np.float64)
    at_zero = np.sign(g) * np.maximum(np.abs(g) - l1, 0.0)
    return np.where(w != 0.0, g + l1 * np.sign(w), at_zero)


def fixed_effect_gradient(block, margins, labels, l2):
    """The smooth gradient of the fixed effect [d + 1] at every row's
    margin: X^T (exp(m) - y) + l2 w, the intercept's entry last and
    without the L2 term."""
    indptr, cols, vals, w, _reg_weight = block
    w = np.asarray(w, np.float64)
    r = np.exp(margins) - labels
    g = np.empty(len(w))
    g[:-1] = plain.csr_t_dot(indptr, cols, vals, r, len(w) - 1) + l2 * w[:-1]
    g[-1] = np.sum(r)
    return g


def fixed_effect_end(block, own_scores, seen, labels, alpha):
    """Where the fixed effect's solve ended, ``seen`` being what its
    solver saw beside its own scores (the exposure's log and the other
    coordinates' scores): a dict of

    ``value``: the objective its solver minimises, L1 term included;
    ``kkt_norm``: the norm of the KKT residual at its coefficients;
    ``kkt_norm_at_zero``: the same at zero coefficients;
    ``inside``: [d + 1] bool, the coordinates whose smooth gradient at
    the end lies strictly inside the L1 term's subdifferential at zero
    (``|g| < l1``; never the intercept).  Such a coordinate, were it not
    zero, has a residual of at least ``l1 - |g|`` pushing it to zero:
    an orthant-wise solver that starts at zero never moves it off, and
    clips to exactly zero one that crosses."""
    labels = np.asarray(labels, np.float64)
    w = np.asarray(block[3], np.float64)
    l1, l2 = split_weights(block[4], alpha)
    l1_by_coordinate = np.full(len(w), l1)
    l1_by_coordinate[-1] = 0.0
    z = seen + own_scores
    g = fixed_effect_gradient(block, z, labels, l2)
    g0 = fixed_effect_gradient(block[:3] + (np.zeros_like(w), block[4]),
                               seen, labels, l2)
    value = (float(np.sum(np.exp(z) - labels * z))
             + 0.5 * l2 * float(np.sum(w[:-1] ** 2))
             + l1 * float(np.sum(np.abs(w[:-1]))))
    return {
        "value": value,
        "kkt_norm": float(np.linalg.norm(
            kkt_residual(g, w, l1_by_coordinate))),
        "kkt_norm_at_zero": float(np.linalg.norm(
            kkt_residual(g0, np.zeros_like(w), l1_by_coordinate))),
        "inside": np.abs(g) < l1_by_coordinate,
    }


def random_effect_end(block, own_scores, seen, labels):
    """(norm of the gradient of a random effect's objective at its
    coefficients, the same at zero coefficients): the summed Poisson
    loss of its rows plus 1/2 lambda |coefs|^2."""
    labels = np.asarray(labels, np.float64)
    x, row_ids, entity_ids, coefs, lam = block
    coefs = np.asarray(coefs, np.float64)
    g = plain.entity_t_dot(x, row_ids, entity_ids,
                           np.exp(seen + own_scores) - labels) + lam * coefs
    g0 = plain.entity_t_dot(x, row_ids, entity_ids, np.exp(seen) - labels)
    return float(np.linalg.norm(g)), float(np.linalg.norm(g0))


def penalty(fixed, random_effects, alpha):
    """The objective's regularisation term: elastic net on the fixed
    effect (intercept left out), L2 on every random effect."""
    w = np.asarray(fixed[3], np.float64)[:-1]
    l1, l2 = split_weights(fixed[4], alpha)
    total = 0.5 * l2 * float(np.sum(w ** 2)) + l1 * float(np.sum(np.abs(w)))
    for *_, coefs, lam in random_effects:
        total += 0.5 * lam * float(np.sum(np.asarray(coefs, np.float64) ** 2))
    return total


def check(*, valid_margins, valid_labels, reported_loss, train_margins,
          train_labels, train_penalty, true_train_margins, optimality,
          fixed_effect, baseline_loss, zeros, limits):
    """Six conditions, all needed for ``correct``:

    (a) ``loss_agrees``: the plain mean Poisson loss of the exported
        coefficients on the validation rows, exposures in, is the
        program's reported ``POISSON_LOSS`` within ``LOSS_RTOL``;
    (b) ``objective_reached``: the objective per training row at the
        exported coefficients is at most the Poisson loss of the
        generating log-rates on the same rows plus ``objective_gap``;
    (c) ``optimal_with_exposure``: ``optimality`` gives, by coordinate,
        (residual norm at the state its solver saw, the same at zero
        coefficients), exposure in on both: the KKT residual for the
        fixed effect, the gradient for a random effect; the first is at
        most the coordinate's ``optimality_rtol`` of the second;
    (d) ``beats_baseline``: the validation loss is under
        ``baseline_loss``, for a cell the loss of the one-number model
        on the same rows (``loss_of_the_pooled_rate``), by at least
        ``loss_gain_floor``.  The seed's own baseline and not a fixed
        ceiling: a heavy draw of validation counts moves both losses
        together, by more than the room between them;
    (e) ``fixed_effect_exact``: ``fixed_effect`` gives what the fit
        itself computed through its own plans (its training scores, its
        solver's last pseudo-gradient norm) as relative distances from
        the plain numbers; each that ``fixed_effect_rtol`` names is at
        most its limit;
    (f) ``zeros_exact``: ``zeros`` gives ``not_zero``, the share of the
        coordinates the reference's KKT calls zero
        (``fixed_effect_end``'s ``inside``) that the export does not
        hold at an exact 0.0, at most ``zero_rtol``; and the program's
        own ``nonzero_coefficients`` is the export's count.

    ``limits`` holds ``objective_gap``, ``optimality_rtol`` (by
    coordinate), ``loss_gain_floor``, ``fixed_effect_rtol`` (by
    reading) and ``zero_rtol``."""
    plain_loss = mean_poisson_loss(valid_margins, valid_labels)
    n = len(train_labels)
    objective = (mean_poisson_loss(train_margins, train_labels)
                 + train_penalty / n)
    true_loss = mean_poisson_loss(true_train_margins, train_labels)
    relative = {name: found / at_zero
                for name, (found, at_zero) in optimality.items()}
    fixed_effect = fixed_effect or {}
    compared = {
        "loss_difference": {
            "value": abs(plain_loss - reported_loss) / abs(plain_loss),
            "limit": LOSS_RTOL},
        "objective_gap": {"value": objective - true_loss,
                          "limit": limits["objective_gap"]},
        "loss_gain": {"value": baseline_loss - plain_loss,
                      "at_least": limits["loss_gain_floor"]},
        "not_zero": {"value": zeros["not_zero"],
                     "limit": limits["zero_rtol"]},
        "nonzero_miscount": {
            "value": (None if zeros["counted"] is None
                      else abs(zeros["counted"] - zeros["exported"])),
            "limit": 0},
    }
    for name, value in relative.items():
        compared["optimality." + name] = {
            "value": value, "limit": limits["optimality_rtol"][name]}
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
        "loss_agrees": holds("loss_difference"),
        "objective_reached": holds("objective_gap"),
        "optimal_with_exposure": holds(
            *("optimality." + name for name in relative)),
        "beats_baseline": holds("loss_gain"),
        "fixed_effect_exact": holds(
            *("fixed_effect." + name
              for name in limits["fixed_effect_rtol"])),
        "zeros_exact": holds("not_zero", "nonzero_miscount"),
    }
    return {
        "plain_loss": plain_loss, "reported_loss": float(reported_loss),
        "objective_per_row": objective, "true_rate_loss": true_loss,
        "objective_gap": objective - true_loss,
        "baseline_loss": baseline_loss,
        "optimality_rel": relative, "fixed_effect_rel": fixed_effect,
        "zeros": zeros, "conditions": conditions,
        "correct": all(conditions.values()), "compared": compared,
    }
