"""The plain reference of a random effect retrained toward yesterday's
posterior: float64 numpy, nothing of photon_ml_tpu, beside
``projected.py`` (its key-join tables and margins) and ``plain.py``.

A random effect is as ``projected.py`` has it: rows as CSR over the
shard's GLOBAL columns, every row's entity id, a model as a table
``(keys, values)`` sorted by ``key = entity id * width + global
column``.  Yesterday's model is such a table with two value columns,
``(keys, means, variances)``.  Today's coefficients are the pairs
(entity, column) that today's rows hold; each has

  f_e(w) = sum over its entity's rows of logloss(y, o + x . w)
           + lambda / 2 |w|^2
           + lambda_p / 2 sum over the pairs yesterday had
                                    (w - mu)^2 / sigma^2

with mu and sigma^2 yesterday's mean and variance of the same pair: a
pair yesterday did not have (a new entity, or a column new to the
entity) has no prior term, and a pair only yesterday had does not
exist today.  The prior is added to the L2 term.  Today's SIMPLE
variance of a pair is 1 / (its diagonal of the Hessian of f_e), the
prior's precision included.  The warm start is yesterday's mean where
there is one, else 0.
"""

import numpy as np

from benchmark.reference import projected


class Rows:
    """One random effect's rows in key space: the distinct pairs its
    entries belong to, and for every entry its row, its pair and its
    value."""

    def __init__(self, indptr, cols, vals, row_entity, width):
        indptr = np.asarray(indptr, np.int64)
        self.n = len(indptr) - 1
        self.width = np.int64(width)
        self.pairs, self.entry_pair = np.unique(
            projected.pair_keys(indptr, cols, row_entity, width),
            return_inverse=True)
        self.entry_row = np.repeat(np.arange(self.n), np.diff(indptr))
        self.vals = np.asarray(vals, np.float64)

    def margins(self, at):
        """[n] x . w of every row, ``at`` the pairs' coefficients."""
        return np.bincount(self.entry_row, weights=self.vals
                           * at[self.entry_pair], minlength=self.n)

    def transposed(self, r):
        """[pairs] X^T r."""
        return np.bincount(self.entry_pair, weights=r[self.entry_row]
                           * self.vals, minlength=len(self.pairs))

    def squares_transposed(self, r):
        """[pairs] (X * X)^T r: the Hessian's diagonal of the data."""
        return np.bincount(self.entry_pair, weights=r[self.entry_row]
                           * self.vals ** 2, minlength=len(self.pairs))


def at_keys(table, keys):
    """[len(keys)] the table's values at ``keys`` (sorted), 0 where it has
    no such key; one pass where the table's keys are ``keys`` already
    (today's export against today's pairs)."""
    if len(table[0]) == len(keys) and np.array_equal(table[0], keys):
        return np.asarray(table[1], np.float64)
    return projected.looked_up(table, keys)


def prior_at(yesterday, keys):
    """(means, precisions) of yesterday's table at ``keys``: 0 and 0
    where it has no such pair."""
    table_keys, means, variances = yesterday
    if len(table_keys) == 0:
        return np.zeros(len(keys)), np.zeros(len(keys))
    at = np.minimum(np.searchsorted(table_keys, keys), len(table_keys) - 1)
    known = table_keys[at] == keys
    return (np.where(known, means[at], 0.0),
            np.where(known, 1.0 / np.where(known, variances[at], 1.0), 0.0))


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def value_and_gradient(rows, at, offsets, labels, reg_weight, prior,
                       prior_weight):
    """(f, its gradient over the pairs) at the pairs' coefficients
    ``at``, every row's margin ``offsets + x . at``; ``prior`` is
    ``prior_at`` of the pairs."""
    labels = np.asarray(labels, np.float64)
    z = offsets + rows.margins(at)
    mu, precision = prior
    value = (float(np.sum(np.logaddexp(0.0, z) - labels * z))
             + 0.5 * reg_weight * float(np.sum(at ** 2))
             + 0.5 * prior_weight * float(np.sum(precision * (at - mu) ** 2)))
    gradient = (rows.transposed(_sigmoid(z) - labels) + reg_weight * at
                + prior_weight * precision * (at - mu))
    return value, gradient


def coordinate_end(rows, at, offsets, labels, reg_weight, prior,
                   prior_weight):
    """(f at the pairs' coefficients ``at``, the norm of its gradient
    there, the same norm at the warm start): ``offsets`` [n] is every
    row's margin from the other coordinates as the solver saw them,
    ``prior`` is ``prior_at`` of the pairs."""
    value, gradient = value_and_gradient(
        rows, at, offsets, labels, reg_weight, prior, prior_weight)
    _value, at_start = value_and_gradient(
        rows, prior[0], offsets, labels, reg_weight, prior, prior_weight)
    return value, float(np.linalg.norm(gradient)), float(
        np.linalg.norm(at_start))


def variances(rows, at, offsets, labels, reg_weight, prior, prior_weight):
    """[pairs] SIMPLE variances at the pairs' coefficients ``at``,
    ``offsets`` the other coordinates' margins."""
    p = _sigmoid(offsets + rows.margins(at))
    return 1.0 / (rows.squares_transposed(p * (1.0 - p)) + reg_weight
                  + prior_weight * prior[1])


def largest_relative_error(got, want):
    """Largest |got - want| / want, ``want`` > 0."""
    return float((np.abs(got - want) / want).max()) if len(want) else 0.0


def solve(rows, offsets, labels, reg_weight, yesterday, prior_weight,
          iterations=30):
    """Today's table by Newton's method, entity by entity, from the
    warm start: (pairs, coefficients).  For tiny problems: a dense
    Hessian an entity."""
    labels = np.asarray(labels, np.float64)
    mu, precision = prior_at(yesterday, rows.pairs)
    w = mu.copy()
    owner = rows.pairs // rows.width
    for e in np.unique(owner):
        mine = np.flatnonzero(owner == e)
        entries = np.isin(rows.entry_pair, mine)
        row_ids = np.unique(rows.entry_row[entries])
        x = np.zeros((len(row_ids), len(mine)))
        np.add.at(x, (np.searchsorted(row_ids, rows.entry_row[entries]),
                      np.searchsorted(mine, rows.entry_pair[entries])),
                  rows.vals[entries])
        o, y = offsets[row_ids], labels[row_ids]
        d = reg_weight + prior_weight * precision[mine]

        def f(v):
            z = o + x @ v
            return (np.sum(np.logaddexp(0.0, z) - y * z)
                    + 0.5 * reg_weight * v @ v
                    + 0.5 * prior_weight * np.sum(
                        precision[mine] * (v - mu[mine]) ** 2))

        v = w[mine]
        for _ in range(iterations):
            p = _sigmoid(o + x @ v)
            g = x.T @ (p - y) + reg_weight * v + prior_weight * precision[
                mine] * (v - mu[mine])
            h = x.T @ (x * (p * (1 - p))[:, None]) + np.diag(d)
            step = np.linalg.solve(h, g)
            a = 1.0
            while f(v - a * step) > f(v) and a > 1e-10:
                a /= 2
            v = v - a * step
        w[mine] = v
    return rows.pairs, w
