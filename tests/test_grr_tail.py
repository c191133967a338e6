"""The planner's three column classes (ISSUE 30): hot (dense, MXU),
planned (supertiles over a compact remap of its own columns) and tail
(columns too sparse to plan, COO on XLA's gather and segment-sum).

The inputs are the benchmark's ``game5-kdd12`` configuration at its
``rehearsal_params`` (eleven one-hot fields, 199,584 columns, 6,000
rows).  At 6,000 rows there is one row window, and the economy bound
(``ECONOMY_SLOTS_PER_ENTRY`` slots per entry) calls no column too
sparse: four slots hold a column of one entry.  The tests that need a
tail lower the bound to 2, so that a column of one entry is tail, as a
column of eight entries or fewer is at the cell's 3.185e6 rows; one
test shows the unpatched rule at a row count where it bites.
Nothing timed here is a performance number."""

import hashlib
import os
import sys
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import manifest as manifests  # noqa: E402
from photon_ml_tpu import native  # noqa: E402
from photon_ml_tpu.data import grr  # noqa: E402
from photon_ml_tpu.data.batch import make_sparse_batch  # noqa: E402

RTOL = 1e-5


def _rehearsal(config_name, seed=7):
    """(train, valid, truth) of a benchmark configuration at its
    rehearsal size."""
    config = manifests.load_json(os.path.join(
        REPO, "benchmark", "configs", config_name + ".json"))
    generator = manifests.load_module(os.path.join(
        REPO, "benchmark", "generators",
        config["generator"]["name"] + ".py"))
    return generator.make(seed, **dict(config["generator"]["params"],
                                       **config["rehearsal_params"]))


def _ell(train):
    """The fixed effect's ELL arrays with the intercept column, as
    ``_prepare_fixed`` and ``make_sparse_batch`` make them."""
    dim = train.feature_dim("global")
    rows = train.features["global"].with_constant_col(dim)
    cols, vals = rows.to_ell()
    return rows, cols, vals, dim + 1


def _numpy_products(cols, vals, dim, w, r, square=False):
    """(X.w, X^T r) in float64 from the ELL arrays."""
    v = vals.astype(np.float64).reshape(-1)
    v = v * v if square else v
    c = cols.reshape(-1)
    row = np.repeat(np.arange(cols.shape[0]), cols.shape[1])
    return (np.bincount(row, weights=v * w[c], minlength=cols.shape[0]),
            np.bincount(c, weights=v * r[row], minlength=dim))


def _close(got, want):
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float64) - want).max() <= RTOL * scale


@pytest.fixture
def sparse_bound(monkeypatch):
    """One entry does not pay for a column's slots: a tail at 6,000
    rows."""
    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)


@pytest.fixture(scope="module")
def kdd12():
    train, _valid, _truth = _rehearsal("game5-kdd12")
    return (train,) + _ell(train)


@pytest.fixture
def tailed_pair(kdd12, sparse_bound):
    _train, _rows, cols, vals, dim = kdd12
    return grr.build_grr_pair(cols, vals, dim)


# -- (a) the contractions at the rehearsal widths ---------------------------

def test_batch_contractions_match_float64_numpy(kdd12, sparse_bound):
    train, rows, cols, vals, dim = kdd12
    batch = make_sparse_batch(rows, dim, train.labels, grr=True,
                              keep_ell=False)
    pair = batch.grr
    assert pair.hot_ids.shape[0] and pair.planned_ids.shape[0]
    assert pair.tail.nnz and pair.dim == dim
    rng = np.random.default_rng(5)
    w, r = rng.normal(size=dim), rng.normal(size=cols.shape[0])
    xw, xtr = _numpy_products(cols, vals, dim, w, r)
    w32, r32 = jnp.asarray(w, jnp.float32), jnp.asarray(r, jnp.float32)
    _close(batch.x_dot(w32), xw)
    _close(batch.margins(w32), xw)
    _close(batch.xt_dot(r32), xtr)
    _x2w, x2tr = _numpy_products(cols, vals * 3.0, dim, w, r, square=True)
    scaled = grr.build_grr_pair(cols, vals * 3.0, dim)
    assert scaled.tail.nnz == pair.tail.nnz
    _close(scaled.squared().t_dot(r32), x2tr)
    _close(scaled.squared().dot(w32), _x2w)
    # the custom VJP: the transpose of X.w is the other direction
    grad = jax.grad(lambda v: jnp.vdot(pair.dot(v), r32))(w32)
    _close(grad, xtr)


def test_margins_carried_through_a_solve_match_float64_numpy(
        kdd12, sparse_bound):
    """The L-BFGS line search walks ``m + a.X.d`` (ISSUE 31): after 30
    float32 iterations the margins the solver carries (what it hands
    the gradient of its last accepted point) are still X.w + o of the
    coefficients it returns, in float64, and every column class is in
    them."""
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.base import OptimizerConfig
    from photon_ml_tpu.optim.lbfgs import lbfgs_solve
    from photon_ml_tpu.optim.problem import as_margin_split

    train, rows, cols, vals, dim = kdd12
    offsets = np.random.default_rng(8).normal(0, 0.5, cols.shape[0])
    batch = make_sparse_batch(rows, dim, train.labels, offsets=offsets,
                              grr=True, keep_ell=False)
    pair = batch.grr
    obj = GLMObjective(loss=losses.LOGISTIC,
                       reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext.identity())
    config = OptimizerConfig(max_iters=30, tolerance=0.0)
    seen = []   # (margins, w) of every gradient the solve took

    def solve(b, w0):
        split = as_margin_split(obj, b)

        def value_and_grad(m, w):
            jax.debug.callback(lambda m, w: seen.append((m, w)), m, w,
                               ordered=True)
            return split.value_and_grad(m, w)
        return lbfgs_solve(split._replace(value_and_grad=value_and_grad),
                           w0, config)

    result = jax.jit(solve)(batch, jnp.zeros(dim, jnp.float32))
    jax.effects_barrier()
    assert int(result.iterations) == 30 and len(seen) == 31
    # the last accepted point's (a rejected search leaves the carry be)
    carried = next(m for m, w_at in reversed(seen)
                   if np.array_equal(w_at, result.w))
    assert carried.dtype == np.float32
    w = np.asarray(result.w, np.float64)
    xw, _xtr = _numpy_products(cols, vals, dim, w, np.zeros(cols.shape[0]))
    _close(carried, xw + offsets)
    # by column class: margins that had lost any one class's share
    # would not pass
    tail_ids = np.unique(np.asarray(pair.tail.col_seg))
    for ids in (np.asarray(pair.hot_ids), np.asarray(pair.planned_ids),
                tail_ids):
        without = w.copy()
        without[ids] = 0.0
        short = _numpy_products(cols, vals, dim, without,
                                np.zeros(cols.shape[0]))[0] + offsets
        with pytest.raises(AssertionError):
            _close(carried, short)


# -- (b) every entry in exactly one class -----------------------------------

def test_every_entry_lands_in_exactly_one_class(kdd12, tailed_pair):
    _train, _rows, cols, vals, dim = kdd12
    pair = tailed_pair
    hot, planned = np.asarray(pair.hot_ids), np.asarray(pair.planned_ids)
    tail_cols = np.unique(np.asarray(pair.tail.col_seg))
    active = np.unique(cols[vals != 0])
    np.testing.assert_array_equal(
        np.sort(np.concatenate([hot, planned, tail_cols])), active)
    assert np.all(np.diff(planned) > 0)
    counts = np.bincount(cols[vals != 0], minlength=dim)
    nnz = int(counts.sum())
    assert int(counts[hot].sum()) == np.count_nonzero(np.asarray(pair.x_hot))
    assert int(counts[tail_cols].sum()) == pair.tail.nnz
    planned_stats = pair.row_dir.plan_stats()
    mid = pair.col_mid.plan_stats()["entries"] if pair.col_mid else 0
    assert planned_stats["entries"] == int(counts[planned].sum()) \
        == pair.col_dir.plan_stats()["entries"] + mid
    assert (np.count_nonzero(np.asarray(pair.x_hot))
            + planned_stats["entries"] + pair.tail.nnz) == nnz
    # the plans are the planned class's size, not the width's
    assert pair.col_dir.n_segments == planned.size < dim // 10
    assert pair.row_dir.table_len == planned.size
    # both orders hold the same entries, each sorted by its segment
    t = pair.tail
    assert np.all(np.diff(np.asarray(t.row_seg)) >= 0)
    assert np.all(np.diff(np.asarray(t.col_seg)) >= 0)
    by_row = sorted(zip(*(np.asarray(a).tolist() for a in
                          (t.row_seg, t.row_idx, t.row_val))))
    by_col = sorted(zip(*(np.asarray(a).tolist() for a in
                          (t.col_idx, t.col_seg, t.col_val))))
    assert by_row == by_col


def test_the_three_partial_products_add_up(kdd12, tailed_pair):
    _train, _rows, cols, vals, dim = kdd12
    pair = tailed_pair
    rng = np.random.default_rng(6)
    w, r = rng.normal(size=dim), rng.normal(size=cols.shape[0])
    w32, r32 = jnp.asarray(w, jnp.float32), jnp.asarray(r, jnp.float32)
    xw, xtr = _numpy_products(cols, vals, dim, w, r)
    hot = np.asarray(pair.x_hot @ w32[pair.hot_ids], np.float64)
    planned = np.asarray(pair.row_dir.contract(w32[pair.planned_ids]),
                         np.float64)
    tail = np.asarray(pair.tail.dot(w32), np.float64)
    for part in (hot, planned, tail):
        assert np.abs(part).max() > 0
    _close(hot + planned + tail, xw)
    # each class alone is its columns' share of the uncut product
    for ids, part in ((np.asarray(pair.hot_ids), hot),
                      (np.unique(np.asarray(pair.tail.col_seg)), tail)):
        only = np.zeros(dim)
        only[ids] = w[ids]
        _close(part, _numpy_products(cols, vals, dim, only, r)[0])
    # the gradient: each class writes its own columns and no other
    full = np.asarray(pair.t_dot(r32), np.float64)
    _close(full, xtr)
    tail_only = np.asarray(pair.tail.t_dot(r32), np.float64)
    others = np.setdiff1d(np.arange(dim),
                          np.unique(np.asarray(pair.tail.col_seg)))
    assert not tail_only[others].any()


def test_classification_follows_the_economy_bound():
    """The rule itself, on counts: with ``w`` row windows a column is
    tail when ``MIN_CAP * w`` slots are more than
    ``ECONOMY_SLOTS_PER_ENTRY`` an entry."""
    counts = np.array([0, 1, 8, 9, 50, 100000, 3, 0])
    windows = 195   # 3,185,000 rows: 4 * 195 / 96 = 8.125 entries
    classes = grr.classify_columns(counts, windows, hot_threshold=9360,
                                   max_hot=128)
    np.testing.assert_array_equal(classes.hot, [5])
    np.testing.assert_array_equal(classes.tail, [1, 2, 6])
    np.testing.assert_array_equal(classes.planned, [3, 4])
    assert (classes.active, classes.planned_nnz, classes.tail_nnz) \
        == (6, 59, 12)
    # one row window: four slots hold any column, nothing is tail, and
    # eight columns in one table window need no remap
    small = grr.classify_columns(counts, 1, hot_threshold=9360, max_hot=128)
    assert small.tail.size == 0 and small.planned is None
    assert small.planned_nnz == 71
    # no tail, but the planned columns need half the table windows or
    # fewer: the plans follow them, not the width
    wide = np.zeros(5 * grr.WIN, np.int64)
    wide[::7] = 40
    classes = grr.classify_columns(wide, 1, hot_threshold=9360, max_hot=128)
    assert classes.tail.size == 0
    np.testing.assert_array_equal(classes.planned, np.flatnonzero(wide))


def test_the_unpatched_rule_finds_a_tail_where_rows_are_many():
    """No patch: 409,600 rows are 25 row windows, 100 slots for a
    column of one entry, over the bound of 96."""
    n, dim = 25 * grr.WIN, 300000
    rng = np.random.default_rng(3)
    cols = np.stack([rng.integers(0, 1000, n),
                     1000 + (299000 * rng.random(n) ** 3).astype(np.int64)],
                    axis=1).astype(np.int32)
    vals = np.ones((n, 2), np.float32)
    pair = grr.build_grr_pair(cols, vals, dim)
    counts = np.bincount(cols.reshape(-1), minlength=dim)
    singles = np.flatnonzero(counts == 1)
    assert singles.size > 1000
    np.testing.assert_array_equal(
        np.unique(np.asarray(pair.tail.col_seg)), singles)
    assert pair.tail.nnz == singles.size
    r = rng.normal(size=n)
    _close(pair.t_dot(jnp.asarray(r, jnp.float32)),
           _numpy_products(cols, vals, dim, np.zeros(dim), r)[1])


# -- (c) an input without sparse columns: the parent's bytes ----------------

# sha256 over (dtype, shape, bytes) of every leaf of the pair that the
# PARENT commit (ccd951b, PLANNER_VERSION 1) builds, through the native
# library, from game5-kdd's rehearsal (seed 7) with its intercept.
PARENT_LEAVES = 32
PARENT_SHA256 = \
    "f834e806138fd1ff432cabd9c7fdaca1f5921199ce80fc91a4eead72cd8e035b"


@pytest.mark.skipif(native.lib() is None,
                    reason="the fingerprint is the native planner's")
def test_an_input_without_sparse_columns_gives_the_parents_bytes():
    train, _valid, _truth = _rehearsal("game5-kdd")
    _rows, cols, vals, dim = _ell(train)
    pair = grr.build_grr_pair(cols, vals, dim)
    assert pair.tail is None and pair.planned_ids is None
    assert pair.width is None and pair.dim == dim
    leaves = jax.tree_util.tree_leaves(pair)
    digest = hashlib.sha256()
    for leaf in leaves:
        a = np.asarray(leaf)
        digest.update(str((a.dtype, a.shape)).encode())
        digest.update(a.tobytes())
    assert len(leaves) == PARENT_LEAVES
    assert digest.hexdigest() == PARENT_SHA256


# -- (d) host memory of a plan build follows nnz, not d ---------------------

def test_plan_build_memory_follows_nnz_not_width():
    """d = 5e6, 20,000 rows of 8: 160,000 entries.  The parent's
    planner blocks the column direction into a supertile for every
    4,096 columns, 1,221 of them by two table windows (280 MB of plan
    arrays alone; its traced peak here is over 1 GB).  Now the plans are
    built over the 150,000 columns that hold an entry; what still
    follows d is a few vectors of d numbers (the counts and the lookup
    from a column to its class: 8 + 8 + 4 + 1 bytes a column)."""
    n, k, dim = 20000, 8, 5_000_000
    rng = np.random.default_rng(1)
    cols = np.sort(((dim - k) * rng.random((n, k)) ** 1.5).astype(np.int64),
                   axis=1)
    for j in range(1, k):
        bump = cols[:, j] <= cols[:, j - 1]
        cols[bump, j] = cols[bump, j - 1] + 1
    cols = cols.astype(np.int32)
    vals = np.ones((n, k), np.float32)
    tracemalloc.start()
    try:
        pair = grr.build_grr_pair(cols, vals, dim)
        jax.block_until_ready(pair)
        _now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    per_column, per_entry = 32, 1500
    assert peak < per_column * dim + per_entry * n * k, peak
    assert pair.planned_ids is not None and pair.width == dim
    assert pair.col_dir.n_segments == pair.planned_ids.shape[0] < 160001
    r = rng.normal(size=n)
    _close(pair.t_dot(jnp.asarray(r, jnp.float32)),
           _numpy_products(cols, vals, dim, np.zeros(dim), r)[1])


# -- (e) the sharded and the chunked builders -------------------------------

def test_sharded_builder_agrees_with_the_resident_one(kdd12, sparse_bound):
    _train, _rows, cols, vals, dim = kdd12
    n_shards = 4
    per = cols.shape[0] // n_shards
    shard_cols = [cols[i * per:(i + 1) * per] for i in range(n_shards)]
    shard_vals = [vals[i * per:(i + 1) * per] for i in range(n_shards)]
    pairs = grr.build_sharded_grr_pairs(shard_cols, shard_vals, dim)
    resident = grr.build_grr_pair(cols[:n_shards * per],
                                  vals[:n_shards * per], dim)
    # one classification for every shard, the resident builder's rule
    # on the global counts (the hot threshold is the sharded builder's)
    for pair in pairs:
        np.testing.assert_array_equal(pair.planned_ids, pairs[0].planned_ids)
        assert pair.width == dim and pair.tail.dim == dim
    tail_cols = np.unique(np.concatenate(
        [np.asarray(p.tail.col_seg)[np.asarray(p.tail.col_val) != 0]
         for p in pairs]))
    # four shards of one row window each: a column pays for 16 slots
    counts = np.bincount(cols[:n_shards * per][vals[:n_shards * per] != 0],
                         minlength=dim)
    sparse = (counts > 0) & (grr.ECONOMY_SLOTS_PER_ENTRY * counts
                             < grr.MIN_CAP * n_shards)
    np.testing.assert_array_equal(tail_cols, np.flatnonzero(sparse))
    assert set(np.unique(np.asarray(resident.tail.col_seg))) < set(tail_cols)
    # congruent pytrees: the same structure and leaf shapes
    shapes = [[np.shape(leaf) for leaf in jax.tree_util.tree_leaves(p)]
              for p in pairs]
    assert all(s == shapes[0] for s in shapes)
    assert len({jax.tree_util.tree_structure(p) for p in pairs}) == 1
    rng = np.random.default_rng(8)
    w, r = rng.normal(size=dim), rng.normal(size=n_shards * per)
    w32 = jnp.asarray(w, jnp.float32)
    xw, xtr = _numpy_products(cols[:n_shards * per], vals[:n_shards * per],
                              dim, w, r)
    got = np.concatenate([np.asarray(p.dot(w32)) for p in pairs])
    _close(got, xw)
    _close(np.asarray(resident.dot(w32)), xw)
    partials = sum(
        np.asarray(p.t_dot(jnp.asarray(r[i * per:(i + 1) * per],
                                       jnp.float32)), np.float64)
        for i, p in enumerate(pairs))
    _close(partials, xtr)


def test_chunked_builder_agrees_with_the_resident_one(kdd12, sparse_bound):
    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import ChunkedGLMObjective

    train, rows, _cols, _vals, dim = kdd12
    objective = GLMObjective(loss=losses.LOGISTIC,
                             reg=RegularizationContext.l2(0.7),
                             norm=NormalizationContext.identity())
    resident = make_sparse_batch(rows, dim, train.labels, grr=True)
    assert resident.grr.tail is not None
    ell = make_sparse_batch(rows, dim, train.labels)
    chunked = build_chunked_batch(rows, dim, train.labels, n_chunks=3,
                                  layout="grr")
    w = jnp.asarray(np.random.default_rng(9).normal(0, 0.2, dim),
                    jnp.float32)
    f_e, g_e = objective.value_and_gradient(w, ell)
    f_r, g_r = objective.value_and_gradient(w, resident)
    f_c, g_c = ChunkedGLMObjective(objective, chunked).value_and_gradient(w)
    for f, g in ((f_r, g_r), (f_c, g_c)):
        np.testing.assert_allclose(float(f), float(f_e), rtol=2e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_e),
                                   rtol=2e-4, atol=2e-4)


# -- the plan cache and the spill warning -----------------------------------

def test_a_tailed_pair_round_trips_through_the_plan_cache(
        kdd12, sparse_bound, tmp_path, spans_of):
    _train, _rows, cols, vals, dim = kdd12
    built, first = spans_of(grr.build_grr_pair, cols, vals, dim,
                            cache_dir=str(tmp_path))
    loaded, second = spans_of(grr.build_grr_pair, cols, vals, dim,
                              cache_dir=str(tmp_path))
    assert [e["args"]["cache_hit"] for e in first + second
            if e["name"] == "grr_plan_build"] == [0, 1]
    assert loaded.width == built.width == dim
    assert (loaded.tail.n_rows, loaded.tail.dim) \
        == (built.tail.n_rows, built.tail.dim)
    for a, b in zip(jax.tree_util.tree_leaves(built),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree_util.tree_structure(built) \
        == jax.tree_util.tree_structure(loaded)


def test_a_few_leftover_entries_of_a_large_pair_do_not_warn(caplog):
    """Inside a pair's build a direction's COO residual is weighed
    against the pair's entries: forty of a small mid plan's hundred are
    nothing of a million."""
    import logging

    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.data.grr"):
        with grr.collect_spill_warnings() as scope:
            scope.planned_for(1_000_000)
            scope.note(40, 111)
        assert not caplog.records
        with grr.collect_spill_warnings() as scope:
            scope.planned_for(1_000_000)
            scope.note(60_000, 200_000)
        assert len(caplog.records) == 1
        caplog.clear()
        with grr.collect_spill_warnings() as scope:   # forgotten at exit
            scope.note(40, 111)
        assert len(caplog.records) == 1


# -- the reference holds the tail to account --------------------------------

def test_a_tiny_fit_with_the_tails_coefficients_zeroed_fails(sparse_bound):
    """A fit of the configuration's rehearsal through the GRR layout
    (the tail on its COO path), held to limits set just above what it
    reaches: the same model with the tail columns' coefficients zeroed,
    as a program that dropped the tail would leave them, is not
    ``correct``."""
    import copy
    import dataclasses

    config = manifests.load_json(os.path.join(
        REPO, "benchmark", "configs", "game5-kdd12.json"))
    config["generator"]["params"].update(config["rehearsal_params"])
    config["training_config"]["sparse_layout"] = "GRR"
    config.update(auc_floor=0.55, objective_gap=0.5,
                  gradient_rtol={"global": 0.5, "per_user": 0.5,
                                 "per_item": 0.5})
    traffic = manifests.load_json(os.path.join(
        REPO, "benchmark", "traffic", "fit-cold.json"))
    operation = manifests.load_module(os.path.join(
        REPO, "benchmark", "operations", traffic["operation"] + ".py"))
    generator = manifests.load_module(os.path.join(
        REPO, "benchmark", "generators",
        config["generator"]["name"] + ".py"))
    data = generator.make(3, **config["generator"]["params"])
    state = operation.prepare(config, traffic, data)
    outcome = operation.one(state)
    whole = operation.reference_check(state, outcome)
    assert whole["correct"], whole

    _rows, cols, vals, dim = _ell(state["train"])
    pair = grr.build_grr_pair(cols, vals, dim)
    tail_cols = np.unique(np.asarray(pair.tail.col_seg))
    assert tail_cols.size > 10000

    tight = copy.deepcopy(config)
    tight["objective_gap"] = whole["objective_gap"] + 1e-3
    tight["gradient_rtol"] = {name: 2 * value for name, value
                              in whole["gradient_rel"].items()}
    tight_state = dict(state, config=tight)
    assert operation.reference_check(tight_state, outcome)["correct"]

    model = copy.copy(outcome["model"])
    model.models = dict(model.models)
    part = model.models["global"]
    means = np.asarray(part.coefficients.means).copy()
    assert np.abs(means[tail_cols]).max() > 0     # the tail was learned
    means[tail_cols] = 0.0
    model.models["global"] = dataclasses.replace(
        part, coefficients=dataclasses.replace(
            part.coefficients, means=jnp.asarray(means)))
    check = operation.reference_check(
        tight_state, {"model": model, "auc": outcome["auc"]})
    assert not check["correct"], check
    assert not check["gradient_small"] or not check["objective_reached"]
