"""KDD-Cup-2012-track-2-shaped GAME data: a sparse fixed effect on
power-law columns plus two power-law entity keys (user: intercept and
one feature, item: intercept).

A copy of ``examples/kdd_scale.py``'s ``synthesize`` and ``split`` (the
program's example may change, the yardstick may not), with one change:
the sparsity pattern and the entity of every row come from the
constant ``STRUCTURE_SEED``, and only the values (true coefficients, the
user feature, labels) from ``--seed``.
Array shapes on the device (GRR plan levels, per-entity buckets)
follow from the pattern alone, so every seed runs the same programs
and the same host work on other numbers; were the pattern drawn from
``--seed``, each new seed would compile the per-entity solves anew
(minutes) inside ``setup_s``.
"""

import numpy as np

from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset

STRUCTURE_SEED = 0


def make(seed, *, n, d, nnz_per_row, n_users, n_items, col_exponent,
         entity_exponent, valid_fraction, valid_max):
    """(train, valid, truth): validation is the last ``valid_fraction``
    of the rows, at most ``valid_max``; ``truth`` holds the generating
    margins of the training and the validation rows."""
    k = nnz_per_row
    pattern = np.random.default_rng(STRUCTURE_SEED)
    # Strictly increasing columns within a row: canonical CSR by
    # construction, no sort of n*k elements.
    cols = np.sort(((d - k) * pattern.random((n, k)) ** col_exponent)
                   .astype(np.int64), axis=1)
    for j in range(1, k):
        bump = cols[:, j] <= cols[:, j - 1]
        cols[bump, j] = cols[bump, j - 1] + 1
    fixed = SparseRows.from_flat(np.arange(n + 1, dtype=np.int64) * k,
                                 cols.reshape(-1), np.ones(n * k, np.float32))
    user = (n_users * pattern.random(n) ** entity_exponent).astype(np.int64)
    item = (n_items * pattern.random(n) ** entity_exponent).astype(np.int64)

    rng = np.random.default_rng(seed)
    w_true = np.zeros(d)
    n_active = max(d // 20, 200)
    w_true[rng.choice(d, size=n_active, replace=False)] = rng.normal(
        0, 1.2, n_active)
    u_eff = rng.normal(0, 1.2, n_users)
    i_eff = rng.normal(0, 0.8, n_items)
    x_user = np.concatenate(
        [np.ones((n, 1), np.float32),
         rng.normal(size=(n, 1)).astype(np.float32)], axis=1)
    margins = (w_true[cols].sum(axis=1) + u_eff[user] + i_eff[item] - 1.0)
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(
        np.float32)

    data = GameDataset(
        labels=labels,
        features={"global": fixed, "user_re": x_user,
                  "item_re": np.ones((n, 1), np.float32)},
        entity_ids={"userId": user, "itemId": item},
        feature_dims={"global": d})
    n_valid = min(int(n * valid_fraction), valid_max)
    train = data.take(np.arange(n - n_valid))
    valid = data.take(np.arange(n - n_valid, n))
    return train, valid, {"train_margins": margins[:n - n_valid],
                          "valid_margins": margins[n - n_valid:]}
