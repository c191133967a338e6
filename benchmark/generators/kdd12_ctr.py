"""Click-through rates with impressions as weights, in the shape of KDD
Cup 2012 track 2's own training file: every instance carries a Click
and an Impression count beside its id fields, and the track's task was
to predict the rate.  The rows, columns, entity keys and impressions
are ``kdd12_counts.py``'s, drawn in its order from the same constant,
so they are the ones ``game5-kdd12`` and ``poisson-enet-kdd12`` run
on, to the byte; what that file needed is copied here, as it copied
``kdd12_fields.py``: the generators import nothing of each other.

After the pattern and the impressions, still from the constant: a
linear truth in rate units, drawn where ``kdd12_counts`` draws its
log-rates (the true coefficients of 5 % of the columns, each user's
and item's effect), and the rate of a row, ``clip(b + their sum,
ctr_min, ctr_max)``, with ``b`` set so that the expected clicks are
``click_share`` of the impressions.  From ``--seed``: the user feature
and ``clicks ~ Binomial(impressions, rate)``.  Labels are the clicks
over the impressions, weights the impressions, and there are no
offsets.  Every seed runs the same programs and the same host work.
"""

import os

import numpy as np

from benchmark.harness import manifest as manifests
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset

STRUCTURE_SEED = 0
OPERATION = os.path.join(manifests.BENCH_DIR, "operations", "fit_ctr.py")
# Halvings of the bias's bracket: to well under float64's resolution of
# a rate.
BIAS_HALVINGS = 60


def clicked_share_bias(score, impressions, share, low, high):
    """The ``b`` for which ``sum(impressions * clip(b + score, low,
    high)) / sum(impressions)`` is ``share`` (it grows with ``b``), by
    halving a bracket."""
    lo, hi = low - float(score.max()), high - float(score.min())

    def share_at(b):
        return float(np.dot(impressions, np.clip(b + score, low, high))
                     / impressions.sum())

    for _ in range(BIAS_HALVINGS):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if share_at(mid) < share else (lo, mid)
    return 0.5 * (lo + hi)


def make(seed, *, n, fields, n_users, n_items, col_exponent,
         entity_exponent, valid_fraction, valid_max, impression_exponent,
         impression_max, click_share, coefficient_sd, user_sd, item_sd,
         ctr_min, ctr_max):
    """(train, valid, truth): ``fields`` is a list of (name,
    cardinality); validation is the last ``valid_fraction`` of the rows,
    at most ``valid_max``; ``truth`` holds the generating rates
    (``train_margins``, ``valid_margins``) and the impressions
    (``train_weights``, ``valid_weights``)."""
    # a program whose configuration cannot state TRON's inner cap is
    # refused by the cell's operation before any data is made
    manifests.load_module(OPERATION).refuse_a_program_without_cg_settings()
    cardinality = np.array([c for _name, c in fields], np.int64)
    offset = np.concatenate([[0], np.cumsum(cardinality)])
    d, k = int(offset[-1]), len(fields)
    pattern = np.random.default_rng(STRUCTURE_SEED)
    cols = np.empty((n, k), np.int32)
    for j in range(k):
        cols[:, j] = offset[j] + np.minimum(
            (cardinality[j] * pattern.random(n) ** col_exponent)
            .astype(np.int64), cardinality[j] - 1)
    fixed = SparseRows(indptr=np.arange(n + 1, dtype=np.int64) * k,
                       cols=cols.reshape(-1),
                       vals=np.ones(n * k, np.float32))
    user = (n_users * pattern.random(n) ** entity_exponent).astype(np.int64)
    item = (n_items * pattern.random(n) ** entity_exponent).astype(np.int64)
    impressions = np.minimum(pattern.zipf(impression_exponent, n),
                             impression_max).astype(np.float64)

    w_true = np.zeros(d)
    n_active = max(d // 20, 200)
    w_true[pattern.choice(d, size=n_active, replace=False)] = pattern.normal(
        0, coefficient_sd, n_active)
    u_eff = pattern.normal(0, user_sd, n_users)
    i_eff = pattern.normal(0, item_sd, n_items)

    score = w_true[cols].sum(axis=1) + u_eff[user] + i_eff[item]
    rate = np.clip(score + clicked_share_bias(
        score, impressions, click_share, ctr_min, ctr_max), ctr_min, ctr_max)

    rng = np.random.default_rng(seed)
    x_user = np.concatenate(
        [np.ones((n, 1), np.float32),
         rng.normal(size=(n, 1)).astype(np.float32)], axis=1)
    clicks = rng.binomial(impressions.astype(np.int64), rate)

    data = GameDataset(
        labels=(clicks / impressions).astype(np.float32),
        features={"global": fixed, "user_re": x_user,
                  "item_re": np.ones((n, 1), np.float32)},
        entity_ids={"userId": user, "itemId": item},
        weights=impressions.astype(np.float32),
        feature_dims={"global": d})
    n_valid = min(int(n * valid_fraction), valid_max)
    train = data.take(np.arange(n - n_valid))
    valid = data.take(np.arange(n - n_valid, n))
    return train, valid, {"train_margins": rate[:n - n_valid],
                          "valid_margins": rate[n - n_valid:],
                          "train_weights": impressions[:n - n_valid],
                          "valid_weights": impressions[n - n_valid:]}
