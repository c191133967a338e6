"""The plain reference of a random effect over a sparse shard, each
entity solved in the subspace of the columns it saw: float64 numpy,
nothing of photon_ml_tpu, beside ``plain.py`` (which has everything
else: the fixed effect, the AUC, the log-loss, ``check``).

A random effect here is (indptr, cols, vals, row entity, table,
reg_weight, width): its rows as CSR over the shard's GLOBAL columns,
every row's entity id, and the model as a table ``(keys,
coefficients)`` with ``keys = entity id * width + global column`` sorted
ascending.  No local
column numbering appears: a coefficient belongs to a pair (entity,
global column) and to nothing else, so a program that maps its local
columns back wrongly scores other rows than these do.

margin(row) = sum over the row's entries of value * coefficient[(entity
              of the row, the entry's column)], a pair absent from the
              table contributing zero.
An entity's gradient is taken over the columns that entity saw in these
rows, whether the table has them or not: a column the program left out
of an entity's subspace keeps its gradient at zero coefficients.
"""

import numpy as np


def pair_keys(indptr, cols, row_entity, width):
    """[nnz] ``entity * width + column`` of every stored entry."""
    per_row = np.diff(np.asarray(indptr, np.int64))
    return (np.repeat(np.asarray(row_entity, np.int64), per_row)
            * np.int64(width) + np.asarray(cols, np.int64))


def table(keys, coefficients):
    """The model as ``margins`` takes it: the pairs sorted by key."""
    keys = np.asarray(keys, np.int64)
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    if len(keys) > 1 and not (keys[1:] > keys[:-1]).all():
        raise ValueError("a pair (entity, column) with two coefficients")
    return keys, np.asarray(coefficients, np.float64)[order]


def looked_up(model, keys):
    """[len(keys)] the model's coefficient of each key, 0 if absent."""
    model_keys, coefficients = model
    if len(model_keys) == 0:
        return np.zeros(len(keys))
    at = np.minimum(np.searchsorted(model_keys, keys), len(model_keys) - 1)
    return np.where(model_keys[at] == keys, coefficients[at], 0.0)


def margins(indptr, cols, vals, row_entity, model, width):
    """[n] per-row scores by key join."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    if n == 0 or len(cols) == 0:
        return np.zeros(n)
    terms = looked_up(model, pair_keys(indptr, cols, row_entity, width)) \
        * np.asarray(vals, np.float64)
    out = np.add.reduceat(terms, np.minimum(indptr[:-1], len(terms) - 1))
    out[indptr[1:] == indptr[:-1]] = 0.0
    return out


def penalty(model, reg_weight):
    """The random effect's L2 term."""
    return 0.5 * reg_weight * float(np.sum(model[1] ** 2))


def _value_and_gradient_norm(vals, entry_row, entry_pair, z, at, reg_weight,
                             labels):
    """(objective, norm of its gradient over every distinct (entity,
    column) pair of the rows) with every row's margin ``z`` and the
    pairs' coefficients ``at``; entry i lies in row ``entry_row[i]`` and
    belongs to pair ``entry_pair[i]``."""
    r = 1.0 / (1.0 + np.exp(-z)) - labels
    loss = float(np.sum(np.logaddexp(0.0, z) - labels * z))
    g = np.bincount(entry_pair, weights=r[entry_row] * vals,
                    minlength=len(at)) + reg_weight * at
    return (loss + 0.5 * reg_weight * float(np.sum(at ** 2)),
            float(np.sqrt(np.sum(g ** 2))))


def coordinate_end(block, own_scores, other_margins, labels):
    """``plain.coordinate_end`` for such a random effect: (the
    objective its solvers minimise together, the norm of its gradient
    over every pair the rows hold, at the table's coefficients; the
    same norm with the coefficients at zero).  ``block`` = (indptr,
    cols, vals, row entity, table, reg_weight, width)."""
    indptr, cols, vals, row_entity, model, reg_weight, width = block
    labels = np.asarray(labels, np.float64)
    indptr = np.asarray(indptr, np.int64)
    pairs, entry_pair = np.unique(
        pair_keys(indptr, cols, row_entity, width), return_inverse=True)
    rows = (np.asarray(vals, np.float64),
            np.repeat(np.arange(len(indptr) - 1), np.diff(indptr)),
            entry_pair)
    value, norm = _value_and_gradient_norm(
        *rows, other_margins + own_scores, looked_up(model, pairs),
        reg_weight, labels)
    return value, norm, _value_and_gradient_norm(
        *rows, other_margins, np.zeros(len(pairs)), reg_weight, labels)[1]


def largest_error(held, scores):
    """Largest row error of ``held`` against the plain ``scores``, over
    ``max(1, |score|)``."""
    held = np.asarray(held, np.float64)
    return float((np.abs(held - scores)
                  / np.maximum(1.0, np.abs(scores))).max())
