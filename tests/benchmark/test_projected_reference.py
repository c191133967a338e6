"""``benchmark/reference/projected.py`` (a random effect over a sparse
shard, by key join over (entity, global column) pairs) against
``plain.py`` on the same shard densified: margins, the objective and
both gradient norms agree to 1e-12, for tables that hold every pair,
lack some, and hold a pair no row has.  Small sizes, float64, no
program."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import plain, projected  # noqa: E402

WIDTH, N, ENTITIES = 37, 400, 23


def _shard(seed):
    """(indptr, cols, vals, row entity): rows of 0 to 5 entries over
    ``WIDTH`` columns, entity ids sparse in [0, 1000)."""
    rng = np.random.default_rng(seed)
    per_row = rng.integers(0, 6, N)
    indptr = np.concatenate([[0], np.cumsum(per_row)])
    cols = np.concatenate([np.sort(rng.choice(WIDTH, k, replace=False))
                           for k in per_row]).astype(np.int32)
    vals = rng.normal(size=len(cols)).astype(np.float32)
    ids = np.sort(rng.choice(1000, ENTITIES, replace=False))
    return indptr, cols, vals, ids[rng.integers(0, ENTITIES, N)]


def _dense(indptr, cols, vals):
    x = np.zeros((len(indptr) - 1, WIDTH))
    x[np.repeat(np.arange(len(indptr) - 1), np.diff(indptr)), cols] = vals
    return x


@pytest.fixture(params=["every pair", "some pairs absent", "a stray pair"])
def case(request):
    """(the shard, the table, the same model as ``plain`` takes it:
    sorted entity ids and [E, WIDTH] coefficients)."""
    indptr, cols, vals, row_entity = _shard(11)
    rng = np.random.default_rng(5)
    keys = np.unique(projected.pair_keys(indptr, cols, row_entity, WIDTH))
    if request.param == "some pairs absent":
        keys = keys[rng.random(len(keys)) < 0.7]
    coefficients = rng.normal(size=len(keys))
    if request.param == "a stray pair":
        # a coefficient on a column its entity never saw: it scores no
        # row, and it is in the penalty
        held = set(keys.tolist())
        stray = next(k for k in (int(row_entity[0]) * WIDTH + c
                                 for c in range(WIDTH)) if k not in held)
        keys = np.append(keys, stray)
        coefficients = np.append(coefficients, 0.75)
    entity_ids = np.unique(row_entity)
    dense = np.zeros((len(entity_ids), WIDTH))
    dense[np.searchsorted(entity_ids, keys // WIDTH), keys % WIDTH] = \
        coefficients
    order = rng.permutation(len(keys))  # ``table`` sorts
    return ((indptr, cols, vals, row_entity),
            projected.table(keys[order], coefficients[order]),
            entity_ids, dense, request.param)


def test_margins_are_the_dense_entity_dot(case):
    (indptr, cols, vals, row_entity), model, entity_ids, dense, _what = case
    ours = projected.margins(indptr, cols, vals, row_entity, model, WIDTH)
    theirs = plain.entity_dot(_dense(indptr, cols, vals), row_entity,
                              entity_ids, dense)
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-12)
    assert np.abs(theirs).max() > 0.5


def test_unseen_entities_and_empty_rows_score_zero(case):
    (indptr, cols, vals, row_entity), model, _ids, _dense_w, _what = case
    elsewhere = np.full_like(row_entity, 1001)
    assert not projected.margins(indptr, cols, vals, elsewhere, model,
                                 WIDTH).any()
    empty = np.flatnonzero(np.diff(indptr) == 0)
    assert len(empty) and not projected.margins(
        indptr, cols, vals, row_entity, model, WIDTH)[empty].any()
    assert not projected.margins(indptr, cols, vals, row_entity,
                                 projected.table([], []), WIDTH).any()


def test_coordinate_end_is_the_dense_one(case):
    (indptr, cols, vals, row_entity), model, entity_ids, dense, what = case
    rng = np.random.default_rng(9)
    labels = (rng.random(N) < 0.4).astype(np.float64)
    others = rng.normal(size=N)
    own = projected.margins(indptr, cols, vals, row_entity, model, WIDTH)
    ours = projected.coordinate_end(
        (indptr, cols, vals, row_entity, model, 0.7, WIDTH), own, others,
        labels)
    theirs = plain.coordinate_end(
        (_dense(indptr, cols, vals), row_entity, entity_ids, dense, 0.7),
        own, others, labels)
    if what == "a stray pair":
        # the gradient is over the pairs the rows hold; the stray
        # coefficient's own term, lambda * 0.75, is the dense norm's alone
        value, norm, at_zero = theirs
        theirs = (value - 0.5 * 0.7 * 0.75 ** 2,
                  np.sqrt(norm ** 2 - (0.7 * 0.75) ** 2), at_zero)
    np.testing.assert_allclose(ours, theirs, rtol=1e-12, atol=1e-12)
    assert ours[1] > 1.0 and ours[2] > 1.0


def test_penalty_and_duplicates(case):
    _shard_, model, _ids, dense, _what = case
    assert projected.penalty(model, 0.7) == pytest.approx(
        0.5 * 0.7 * float(np.sum(dense ** 2)), rel=1e-12)
    with pytest.raises(ValueError, match="two coefficients"):
        projected.table([3, 5, 3], [1.0, 2.0, 3.0])


def test_largest_error_is_relative_above_one():
    scores = np.array([0.0, 0.5, -4.0])
    assert projected.largest_error(scores + [1e-3, 0, 0], scores) == \
        pytest.approx(1e-3)
    assert projected.largest_error(scores + [0, 0, 4e-3], scores) == \
        pytest.approx(1e-3)
