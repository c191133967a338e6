"""GLMix data over KDD Cup 2012 track 2's one-hot fields, sharded as the
upstream README's GAME example shards its bags: a global shard of every
feature bag, a per-user shard of the context's and the ad's features, a
per-ad shard of the context's and the user's.  ``UserID`` and ``AdID``
are the random effects' keys and no columns; ``QueryID``, the user
side's other id field, goes with ``UserID``.

The pattern is ``kdd12_fields.py``'s, drawn in its order from the same
constant (every field of ``fields``, the dropped ones too, then the two
entity keys), so the rows, columns and keys of a field that stays are
the ones ``game5-kdd12`` runs on.  What that file needed is copied
here, as it copied ``kdd_powerlaw.py``: the two import nothing of each
other.  Every shard is ``SparseRows`` of entries 1.0; a random effect's
shard ends in a constant column, its per-entity intercept.

Only the numbers come from ``--seed``: the fixed effect's weights, each
entity's intercept, and each entity's slopes on the columns of its
``slope_fields``, which are a counter-based hash of (entity, column,
seed): no [entities x columns] table is ever made.
"""

import os

import numpy as np

from benchmark.harness import manifest as manifests
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset

STRUCTURE_SEED = 0
OPERATION = os.path.join(manifests.BENCH_DIR, "operations",
                         "fit_projected.py")


def hashed_normal(entity, column, seed, salt):
    """Standard normal numbers that depend on (entity, column, seed,
    salt) and nothing else: splitmix64's finaliser over a mix of the
    four gives two uniforms, Box-Muller a normal."""
    def mixed(z):
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))

    def uniform(z):  # (0, 1]
        return ((z >> np.uint64(11)).astype(np.float64) + 1.0) / 2.0 ** 53

    key = (np.asarray(entity).astype(np.uint64)
           * np.uint64(0x9E3779B97F4A7C15)
           + np.asarray(column).astype(np.uint64)
           * np.uint64(0xC2B2AE3D27D4EB4F)
           + np.full(1, seed, np.uint64) * np.uint64(0x165667B19E3779F9)
           + np.full(1, salt, np.uint64) * np.uint64(0xD6E8FEB86659FD93))
    first = mixed(key)
    second = mixed(first + np.uint64(0x9E3779B97F4A7C15))
    return (np.sqrt(-2.0 * np.log(uniform(first)))
            * np.cos(2.0 * np.pi * uniform(second)))


def shard(cols, names, bag, cardinality, constant):
    """(SparseRows, width) of the fields of ``bag`` (names, in the
    order of ``names``) out of ``cols`` [n, fields] of per-field ids:
    field j of the bag owns [offset_j, offset_j + cardinality_j), and
    with ``constant`` a last column holds 1.0 in every row."""
    kept = [names.index(name) for name in bag]
    offset = np.concatenate([[0], np.cumsum(cardinality[kept])])
    n, width = len(cols), int(offset[-1])
    out = np.empty((n, len(kept) + constant), np.int32)
    out[:, :len(kept)] = cols[:, kept] + offset[:-1]
    if constant:
        out[:, -1] = width
    k = out.shape[1]
    return SparseRows(indptr=np.arange(n + 1, dtype=np.int64) * k,
                      cols=out.reshape(-1),
                      vals=np.ones(n * k, np.float32)), width + constant


def make(seed, *, n, fields, key_fields, global_bag, user_bag, ad_bag,
         user_slope_fields, ad_slope_fields, n_users, n_ads, col_exponent,
         entity_exponent, valid_fraction, valid_max, scales):
    """(train, valid, truth): ``fields`` is ``kdd12_fields``' list of
    (name, cardinality), ``key_fields`` those of it that are drawn and
    dropped; the three bags name the fields of each shard; ``scales``
    holds the truth's standard deviations.  Validation is the last
    ``valid_fraction`` of the rows, at most ``valid_max``."""
    # a program whose FitResult hands no descent over is refused by the
    # cell's operation before any data is made
    manifests.load_module(OPERATION).refuse_a_fit_that_hands_nothing_over()
    names = [name for name, _c in fields]
    cardinality = np.array([c for _name, c in fields], np.int64)
    assert not set(key_fields) & set(global_bag + user_bag + ad_bag)
    pattern = np.random.default_rng(STRUCTURE_SEED)
    cols = np.empty((n, len(fields)), np.int64)  # ids inside each field
    for j in range(len(fields)):
        cols[:, j] = np.minimum(
            (cardinality[j] * pattern.random(n) ** col_exponent)
            .astype(np.int64), cardinality[j] - 1)
    user = (n_users * pattern.random(n) ** entity_exponent).astype(np.int64)
    ad = (n_ads * pattern.random(n) ** entity_exponent).astype(np.int64)

    fixed, d = shard(cols, names, global_bag, cardinality, constant=0)
    user_shard, user_dim = shard(cols, names, user_bag, cardinality, 1)
    ad_shard, ad_dim = shard(cols, names, ad_bag, cardinality, 1)

    rng = np.random.default_rng(seed)
    w_true = np.zeros(d)
    n_active = max(d // 20, 200)
    w_true[rng.choice(d, size=n_active, replace=False)] = rng.normal(
        0, scales["fixed"], n_active)
    margins = (w_true[fixed.cols.reshape(n, -1)].sum(axis=1)
               + rng.normal(0, scales["user_intercept"], n_users)[user]
               + rng.normal(0, scales["ad_intercept"], n_ads)[ad] - 1.0)
    for entity, slope_fields, scale, salt in (
            (user, user_slope_fields, scales["user_slope"], 1),
            (ad, ad_slope_fields, scales["ad_slope"], 2)):
        for name in slope_fields:
            j = names.index(name)
            margins += scale * hashed_normal(entity, cols[:, j], seed,
                                             salt * 64 + j)
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(
        np.float32)

    data = GameDataset(
        labels=labels,
        features={"global": fixed, "user_shard": user_shard,
                  "ad_shard": ad_shard},
        entity_ids={"userId": user, "adId": ad},
        feature_dims={"global": d, "user_shard": user_dim,
                      "ad_shard": ad_dim})
    n_valid = min(int(n * valid_fraction), valid_max)
    train = data.take(np.arange(n - n_valid))
    valid = data.take(np.arange(n - n_valid, n))
    return train, valid, {"train_margins": margins[:n - n_valid],
                          "valid_margins": margins[n - n_valid:]}
