"""Click counts with impressions as exposure, in the shape of KDD Cup
2012 track 2's own training file: every instance carries a Click and an
Impression count beside its id fields.  The rows, columns and entity
keys are ``kdd12_fields.py``'s, drawn in its order from the same
constant, so they are the ones ``game5-kdd12`` runs on, to the byte;
what that file needed is copied here, as it copied ``kdd_powerlaw.py``:
the two import nothing of each other.

After the pattern, still from the constant: every row's impressions,
``min(Zipf(impression_exponent), impression_max)``, at least 1 and
heavy-tailed; then the truth as ``kdd12_fields`` draws it (the true
coefficients, each user's and item's effect), and the log-rate of a
row: their sum plus a bias that makes the expected clicks
``click_share`` of the impressions.  From ``--seed``: the user feature
and ``clicks ~ Poisson(impressions * exp(log-rate))``.  The truth is
the constant's and not the seed's because the fit's seconds follow
OWL-QN's count of line-search trials, which the seed moves (the
configuration's ``assumed.truth`` has the readings).  Labels are the
clicks, offsets the log of the impressions, and there are no weights.
Every seed runs the same programs and the same host work.
"""

import os

import numpy as np

from benchmark.harness import manifest as manifests
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset

STRUCTURE_SEED = 0
OPERATION = os.path.join(manifests.BENCH_DIR, "operations",
                         "fit_exposure.py")


def make(seed, *, n, fields, n_users, n_items, col_exponent,
         entity_exponent, valid_fraction, valid_max, impression_exponent,
         impression_max, click_share):
    """(train, valid, truth): ``fields`` is a list of (name,
    cardinality); validation is the last ``valid_fraction`` of the rows,
    at most ``valid_max``; ``truth`` holds the generating log-rates
    (``train_margins``, ``valid_margins``: exposure not in them) and the
    exposures (``train_exposure``, ``valid_exposure``)."""
    # a program whose training drops a dataset's offsets is refused by
    # the cell's operation before any data is made
    manifests.load_module(OPERATION).refuse_a_training_that_drops_offsets()
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
        0, 1.2, n_active)
    u_eff = pattern.normal(0, 1.2, n_users)
    i_eff = pattern.normal(0, 0.8, n_items)

    rng = np.random.default_rng(seed)
    x_user = np.concatenate(
        [np.ones((n, 1), np.float32),
         rng.normal(size=(n, 1)).astype(np.float32)], axis=1)
    log_rate = w_true[cols].sum(axis=1) + u_eff[user] + i_eff[item]
    log_rate += np.log(click_share * impressions.sum()
                       / (impressions * np.exp(log_rate)).sum())
    clicks = rng.poisson(impressions * np.exp(log_rate)).astype(np.float32)

    data = GameDataset(
        labels=clicks,
        features={"global": fixed, "user_re": x_user,
                  "item_re": np.ones((n, 1), np.float32)},
        entity_ids={"userId": user, "itemId": item},
        offsets=np.log(impressions).astype(np.float32),
        feature_dims={"global": d})
    n_valid = min(int(n * valid_fraction), valid_max)
    train = data.take(np.arange(n - n_valid))
    valid = data.take(np.arange(n - n_valid, n))
    return train, valid, {"train_margins": log_rate[:n - n_valid],
                          "valid_margins": log_rate[n - n_valid:],
                          "train_exposure": impressions[:n - n_valid],
                          "valid_exposure": impressions[n - n_valid:]}
