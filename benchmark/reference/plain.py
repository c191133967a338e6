"""The plain reference of a GAME model: float64 numpy, CSR dot products,
nothing of photon_ml_tpu.  ``check`` decides a run's ``correct``.

margin(row) = x_fixed(row) . w  (the intercept is w's last entry)
            + sum over random effects of x_re(row) . coef[entity(row)],
              an entity that training never saw contributing zero.

objective   = sum over rows of the logistic loss at the margins
            + 1/2 lambda_c |coef_c|^2 for every coordinate c, the fixed
              effect's intercept left out (the program's own convention,
              ``ops/objective.py`` and ``estimators/game_estimator.py``).
"""

import numpy as np

# The program sums float32 products on the device (and GRR sums them in
# plan order); this file sums float64 in row order.  Over ~10-30 terms a
# margin differs by ~1e-6 relative, which moves the rank-sum AUC of 1e4
# to 2e5 validation rows by a few 1e-6 (measured on the CPU at 60,000
# rows: 0.75983719 here against the program's 0.75983721).  1e-3 is far
# above that and far below what a dropped coordinate or a wrong
# intercept does (a zeroed block moves AUC by 1e-2 or more).
AUC_ATOL = 1e-3


def csr_dot(indptr, cols, vals, w):
    """[n] row sums of vals * w[cols] for CSR rows, in float64."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    if n == 0 or len(cols) == 0:
        return np.zeros(n)
    terms = np.asarray(w, np.float64)[cols] * vals
    # reduceat sums terms[indptr[i]:indptr[i + 1]]; for an empty row it
    # would give the next row's first term, so those are set to 0.
    out = np.add.reduceat(terms, np.minimum(indptr[:-1], len(terms) - 1))
    out[indptr[1:] == indptr[:-1]] = 0.0
    return out


def entity_dot(x, row_ids, entity_ids, coefs):
    """[n] per-row x . coefs[entity]; ``entity_ids`` is sorted and
    ``coefs[i]`` belongs to ``entity_ids[i]``; unseen entities give 0."""
    entity_ids = np.asarray(entity_ids)
    row_ids = np.asarray(row_ids)
    at = np.minimum(np.searchsorted(entity_ids, row_ids),
                    len(entity_ids) - 1)
    seen = entity_ids[at] == row_ids
    per_row = (np.asarray(x, np.float64)
               * np.asarray(coefs, np.float64)[at]).sum(axis=1)
    return np.where(seen, per_row, 0.0)


def margins(fixed, random_effects):
    """``fixed`` = (indptr, cols, vals, w) with w = [d + 1], intercept
    last; ``random_effects`` = [(x, row_ids, entity_ids, coefs), ...]."""
    indptr, cols, vals, w = fixed
    w = np.asarray(w, np.float64)
    out = csr_dot(indptr, cols, vals, w[:-1]) + w[-1]
    for x, row_ids, entity_ids, coefs in random_effects:
        out = out + entity_dot(x, row_ids, entity_ids, coefs)
    return out


def auc(scores, labels):
    """Area under the ROC curve by the rank-sum, ties at mid-rank."""
    scores = np.asarray(scores, np.float64)
    positive = np.asarray(labels) > 0.5
    n_pos = int(positive.sum())
    n_neg = len(scores) - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(scores, kind="mergesort")
    sorted_scores = scores[order]
    # mid-ranks: first and last position of each run of equal scores
    first = np.flatnonzero(np.r_[True, sorted_scores[1:] != sorted_scores[:-1]])
    last = np.r_[first[1:], len(scores)]
    mid = (first + last + 1) / 2.0
    ranks = np.empty(len(scores))
    ranks[order] = np.repeat(mid, last - first)
    return float((ranks[positive].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def mean_log_loss(margins_, labels):
    """Mean logistic loss of labels in {0, 1} at the given margins."""
    z = np.asarray(margins_, np.float64)
    y = np.asarray(labels, np.float64)
    return float(np.mean(np.logaddexp(0.0, z) - y * z))


def csr_t_dot(indptr, cols, vals, r, d):
    """[d] column sums of vals * r[row] (X^T r) for CSR rows, float64."""
    indptr = np.asarray(indptr, np.int64)
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return np.bincount(cols, weights=np.asarray(r, np.float64)[rows] * vals,
                       minlength=d)[:d]


def entity_t_dot(x, row_ids, entity_ids, r):
    """[E, p] per-entity sums of x * r[row]; rows of unseen entities
    are left out."""
    entity_ids = np.asarray(entity_ids)
    row_ids = np.asarray(row_ids)
    at = np.minimum(np.searchsorted(entity_ids, row_ids),
                    len(entity_ids) - 1)
    seen = entity_ids[at] == row_ids
    x = np.asarray(x, np.float64)
    out = np.zeros((len(entity_ids), x.shape[1]))
    for j in range(x.shape[1]):
        out[:, j] = np.bincount(at[seen], weights=(x[:, j] * r)[seen],
                                minlength=len(entity_ids))
    return out


def penalty(fixed, random_effects):
    """The objective's L2 term.  ``fixed`` = (..., w, reg_weight) and
    each random effect = (..., coefs, reg_weight), as ``margins`` takes
    them with the coordinate's weight appended."""
    *_, w, lam = fixed
    total = 0.5 * lam * float(np.sum(np.asarray(w, np.float64)[:-1] ** 2))
    for *_, coefs, lam in random_effects:
        total += 0.5 * lam * float(np.sum(np.asarray(coefs, np.float64) ** 2))
    return total


def _value_and_gradient_norm(block, z, at, labels):
    """(objective, norm of its gradient) of one coordinate with its
    coefficients at ``at`` and every row's margin ``z``; ``block`` as
    ``penalty`` takes it (five entries for the fixed effect, whose
    first is the CSR ``indptr``; five for a random effect, whose first
    is the dense ``x``)."""
    *rows, coefs, lam = block
    r = 1.0 / (1.0 + np.exp(-z)) - labels
    loss = float(np.sum(np.logaddexp(0.0, z) - labels * z))
    if np.ndim(coefs) == 1:  # fixed effect: intercept last, unregularised
        g = csr_t_dot(*rows, r, len(coefs) - 1) + lam * at[:-1]
        return (loss + 0.5 * lam * float(np.sum(at[:-1] ** 2)),
                float(np.sqrt(np.sum(g ** 2) + np.sum(r) ** 2)))
    g = entity_t_dot(*rows, r) + lam * at
    return (loss + 0.5 * lam * float(np.sum(at ** 2)),
            float(np.sqrt(np.sum(g ** 2))))


def coordinate_end(block, own_scores, other_margins, labels):
    """Where one coordinate's solve ended, the others held: (the
    objective its solver minimises, i.e. the summed logistic loss of
    all training rows plus its own L2 term, at the coordinate's
    coefficients; the norm of that objective's gradient there; the
    same norm with the coordinate's coefficients at zero).
    ``own_scores`` are its rows' scores under its coefficients and
    ``other_margins`` the sum of the other coordinates' scores as its
    solver saw them."""
    labels = np.asarray(labels, np.float64)
    coefs = np.asarray(block[-2], np.float64)
    value, norm = _value_and_gradient_norm(
        block, other_margins + own_scores, coefs, labels)
    return value, norm, _value_and_gradient_norm(
        block, other_margins, np.zeros_like(coefs), labels)[1]


def check(*, valid_margins, valid_labels, train_margins, train_labels,
          train_penalty, true_train_margins, gradients, fixed_effect,
          reported_auc, auc_floor, objective_gap, gradient_rtol,
          fixed_effect_rtol):
    """Five conditions, all needed for ``correct``:

    (a) scoring: the plain AUC of the exported coefficients equals the
        program's reported AUC within AUC_ATOL;
    (b) how far the training got: the objective per training row at the
        exported coefficients is at most the log-loss of the generating
        margins on the same rows plus the configuration's
        ``objective_gap``.  The generating margins are this seed's own
        yardstick, so the bound moves with the seed's noise and can be a
        few 1e-3 wide: fewer iterations or a coordinate's left-out tail
        leave the objective higher;
    (c) how well each solve finished: ``gradients`` gives, by
        coordinate, the norm of the objective's gradient with respect
        to that coordinate's coefficients at the state its solver saw,
        and the same at zero coefficients (``coordinate_end``'s last
        two); the first is at most the
        coordinate's ``gradient_rtol`` of the second.  A solve stopped
        early, or a per-entity solve in a lower precision, stays above
        it;
    (d) the AUC is above the configuration's floor;
    (e) the fixed effect's contractions are the configuration's
        precision: ``fixed_effect`` gives what the fit itself computed
        through its own plans (its training scores, its solver's last
        gradient norm) as relative distances from the plain float64
        numbers at the exported coefficients; each that
        ``fixed_effect_rtol`` names is at most its limit there.  A
        contraction whose result is rounded to bfloat16 is over it a
        hundredfold.  A fit that hands none of it over is not correct.

    ``compared`` lists every number compared beside its limit (a floor
    as ``at_least``), and ``conditions`` the five verdicts."""
    plain_auc = auc(valid_margins, valid_labels)
    n = len(train_labels)
    objective = mean_log_loss(train_margins, train_labels) + train_penalty / n
    true_loss = mean_log_loss(true_train_margins, train_labels)
    relative = {name: g / g0 for name, (g, g0) in gradients.items()}
    fixed_effect = fixed_effect or {}
    compared = {
        "auc_difference": {"value": abs(plain_auc - reported_auc),
                           "limit": AUC_ATOL},
        "objective_gap": {"value": objective - true_loss,
                          "limit": objective_gap},
        "auc": {"value": plain_auc, "at_least": auc_floor},
    }
    for name, value in relative.items():
        compared["gradient." + name] = {"value": value,
                                        "limit": gradient_rtol[name]}
    for name, limit in fixed_effect_rtol.items():
        compared["fixed_effect." + name] = {
            "value": fixed_effect.get(name), "limit": limit}

    def holds(entry):
        if entry["value"] is None or not np.isfinite(entry["value"]):
            return False
        if "at_least" in entry:
            return bool(entry["value"] > entry["at_least"])
        return bool(entry["value"] <= entry["limit"])

    conditions = {
        "auc_agrees": holds(compared["auc_difference"]),
        "objective_reached": holds(compared["objective_gap"]),
        "gradient_small": all(holds(compared["gradient." + name])
                              for name in relative),
        "above_floor": holds(compared["auc"]),
        "fixed_effect_exact": all(holds(compared["fixed_effect." + name])
                                  for name in fixed_effect_rtol),
    }
    # the verdicts are keys of their own too: tests/test_grr_tail.py reads
    # ``gradient_small`` and ``objective_reached`` there
    return {
        "plain_auc": plain_auc, "reported_auc": float(reported_auc),
        "objective_per_row": objective, "true_margin_log_loss": true_loss,
        "objective_gap": objective - true_loss,
        "gradient_rel": relative, "fixed_effect_rel": fixed_effect,
        **conditions, "conditions": conditions,
        "correct": all(conditions.values()),
        "compared": compared,
    }
