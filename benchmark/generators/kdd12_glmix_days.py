"""Two days of GLMix data over KDD Cup 2012 track 2's one-hot fields, for
a daily refresh: day N, on which the previous model was trained, and
day N+1, the day being trained.  Each day is sharded as
``kdd12_glmix.py`` shards it: a global shard of every feature bag, a
per-user shard of the context's and the ad's features, a per-ad shard
of the context's and the user's; ``UserID`` and ``AdID`` are the random
effects' keys and no columns.

The track has no timestamp, so the two days are two draws of rows from
the same populations under the same truth.  Day N+1's pattern is drawn
from ``TODAY_STRUCTURE``, which is ``kdd12_glmix``'s constant, in its
order: its rows, columns, keys, truth and labels are ``glmix-kdd12``'s
for the same seed.  Day N's pattern is drawn the same way from
``YESTERDAY_STRUCTURE``: the same power-law users and ads, so the heavy
entities return and the light ones often do not, and a returning
entity sees some of yesterday's columns again and some new ones.  What
this file needs of ``kdd12_glmix.py`` is copied here: the two import
nothing of each other.

Only the numbers come from ``--seed``: the fixed effect's weights, each
entity's intercept, each entity's slopes on the columns of its
``slope_fields`` (a counter-based hash of (entity, column, seed): no
[entities x columns] table is ever made), one truth for both days; then
day N+1's labels, then day N's.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import manifest as manifests
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.game.dataset import GameDataset

TODAY_STRUCTURE = 0
YESTERDAY_STRUCTURE = 1
OPERATION = os.path.join(manifests.BENCH_DIR, "operations",
                         "fit_refresh.py")


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


def pattern(structure, n, cardinality, n_users, n_ads, col_exponent,
            entity_exponent):
    """(per-field ids [n, fields], user [n], ad [n]) of one day, drawn
    from the constant ``structure`` field by field, then the keys."""
    draw = np.random.default_rng(structure)
    cols = np.empty((n, len(cardinality)), np.int64)
    for j in range(len(cardinality)):
        cols[:, j] = np.minimum(
            (cardinality[j] * draw.random(n) ** col_exponent)
            .astype(np.int64), cardinality[j] - 1)
    user = (n_users * draw.random(n) ** entity_exponent).astype(np.int64)
    ad = (n_ads * draw.random(n) ** entity_exponent).astype(np.int64)
    return cols, user, ad


def make(seed, *, n, fields, key_fields, global_bag, user_bag, ad_bag,
         user_slope_fields, ad_slope_fields, n_users, n_ads, col_exponent,
         entity_exponent, valid_fraction, valid_max, scales):
    """(train, valid, truth) of day N+1, as ``kdd12_glmix.make`` takes
    its parameters; ``truth`` also holds ``yesterday_train``, day N's
    training rows (its first ``n`` less the validation share), on which
    the previous model is fitted."""
    # a program whose random effects take no prior is refused by the
    # cell's operation before any data is made
    manifests.load_module(OPERATION).refuse_a_program_without_entity_priors()
    names = [name for name, _c in fields]
    cardinality = np.array([c for _name, c in fields], np.int64)
    assert not set(key_fields) & set(global_bag + user_bag + ad_bag)
    n_valid = min(int(n * valid_fraction), valid_max)

    rng = np.random.default_rng(seed)
    d = int(cardinality[[names.index(f) for f in global_bag]].sum())
    w_true = np.zeros(d)
    n_active = max(d // 20, 200)
    w_true[rng.choice(d, size=n_active, replace=False)] = rng.normal(
        0, scales["fixed"], n_active)
    user_intercept = rng.normal(0, scales["user_intercept"], n_users)
    ad_intercept = rng.normal(0, scales["ad_intercept"], n_ads)

    def day(structure):
        """One day's ``GameDataset`` fields but its labels, and its
        generating margins."""
        cols, user, ad = pattern(structure, n, cardinality, n_users, n_ads,
                                 col_exponent, entity_exponent)
        fixed, _d = shard(cols, names, global_bag, cardinality, constant=0)
        user_shard, user_dim = shard(cols, names, user_bag, cardinality, 1)
        ad_shard, ad_dim = shard(cols, names, ad_bag, cardinality, 1)
        margins = (w_true[fixed.cols.reshape(n, -1)].sum(axis=1)
                   + user_intercept[user] + ad_intercept[ad] - 1.0)
        for entity, slope_fields, scale, salt in (
                (user, user_slope_fields, scales["user_slope"], 1),
                (ad, ad_slope_fields, scales["ad_slope"], 2)):
            for name in slope_fields:
                j = names.index(name)
                margins += scale * hashed_normal(entity, cols[:, j], seed,
                                                 salt * 64 + j)
        return dict(
            features={"global": fixed, "user_shard": user_shard,
                      "ad_shard": ad_shard},
            entity_ids={"userId": user, "adId": ad},
            feature_dims={"global": d, "user_shard": user_dim,
                          "ad_shard": ad_dim}), margins

    # the two days side by side (numpy's bulk work releases the GIL);
    # the labels after, from ``rng`` in the days' order
    with ThreadPoolExecutor(2) as pool:
        out = list(pool.map(day, (TODAY_STRUCTURE, YESTERDAY_STRUCTURE)))
    (today, yesterday) = [GameDataset(
        labels=(rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(
            np.float32), **fields) for fields, margins in out]
    margins = out[0][1]
    train = today.take(np.arange(n - n_valid))
    valid = today.take(np.arange(n - n_valid, n))
    return train, valid, {
        "train_margins": margins[:n - n_valid],
        "valid_margins": margins[n - n_valid:],
        "yesterday_train": yesterday.take(np.arange(n - n_valid))}
