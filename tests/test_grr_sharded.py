"""Mesh-sharded GRR plans: the fast path IS the distributed path.

Round-3 verdict item #1 / BASELINE north star: per-device GrrPairs over
shard-local rows, gradient partials met by the existing psum.  These
tests check (a) shard-local plan semantics against the global plan,
(b) mesh-uniform structure (congruent pytrees, equal leaf shapes),
(c) the assembled batch through shard_map + DistributedGLMObjective
matches the single-device GRR objective, on the virtual 8-device mesh.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.grr import build_grr_pair, build_sharded_grr_pairs


@pytest.fixture
def rng():
    return np.random.default_rng(3)


def _ell(rng, n, d, k, hot_col=None, skew=False):
    """Synthetic ELL with optional forced-hot column and power-law cols."""
    if skew:
        # Zipf-ish column draw → heavy per-column tails (spill pressure).
        raw = rng.zipf(1.3, (n, k)) % d
        cols = raw.astype(np.int64)
        # De-duplicate within each row by re-rolling dups to random cols.
        for _ in range(4):
            s = np.sort(cols, axis=1)
            dup_rows = (s[:, 1:] == s[:, :-1]).any(axis=1)
            if not dup_rows.any():
                break
            cols[dup_rows] = rng.choice(d, (int(dup_rows.sum()), k),
                                        replace=True)
        # Final pass: force uniqueness per row deterministically.
        base = np.arange(k) * (d // k)
        for i in np.flatnonzero([len(set(r)) < k for r in cols]):
            cols[i] = base + rng.integers(0, d // k, k)
    else:
        block = d // k
        cols = (np.arange(k) * block)[None, :] + rng.integers(
            0, block, (n, k))
    if hot_col is not None:
        cols[:, 0] = hot_col
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    return cols.astype(np.int32), vals


def _pair_dot(pair, w):
    return np.asarray(pair.dot(jnp.asarray(w)))


def _pair_tdot(pair, r):
    return np.asarray(pair.t_dot(jnp.asarray(r)))


@pytest.mark.parametrize("hot,skew", [(None, False), (7, False), (None, True)])
def test_sharded_pairs_match_global(rng, hot, skew):
    n, d, k, n_dev = 1024, 600, 8, 8
    cols, vals = _ell(rng, n, d, k, hot_col=hot, skew=skew)
    per = n // n_dev
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        d, overflow_threshold=64,
    )
    ref = build_grr_pair(cols, vals, d)

    w = rng.normal(0, 1, d).astype(np.float32)
    r = rng.normal(0, 1, n).astype(np.float32)
    # margins: concat of shard-local dots == global dot
    got = np.concatenate([_pair_dot(p, w) for p in pairs])
    np.testing.assert_allclose(got, _pair_dot(ref, w), rtol=2e-5, atol=2e-4)
    # gradient: sum of shard partials == global t_dot
    got_g = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
                for i, p in enumerate(pairs))
    np.testing.assert_allclose(got_g, _pair_tdot(ref, r),
                               rtol=2e-4, atol=5e-4)


def test_sharded_pairs_mesh_uniform(rng):
    """Congruent pytrees + equal leaf shapes: the assembly contract."""
    n, d, k, n_dev = 512, 400, 6, 8
    cols, vals = _ell(rng, n, d, k, hot_col=3)
    per = n // n_dev
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        d,
    )
    t0, s0 = jax.tree.flatten(pairs[0])[1], [
        lf.shape for lf in jax.tree.leaves(pairs[0])]
    for p in pairs[1:]:
        leaves, tdef = jax.tree.flatten(p)
        assert tdef == t0
        assert [lf.shape for lf in leaves] == s0
    # Static metadata forced common
    assert len({p.row_dir.cap for p in pairs}) == 1
    assert len({p.col_dir.cap for p in pairs}) == 1
    # hot ids identical across shards
    for p in pairs[1:]:
        np.testing.assert_array_equal(np.asarray(p.hot_ids),
                                      np.asarray(pairs[0].hot_ids))


def test_pooled_overflow_absorbs_spill(rng):
    """Heavy per-(segment, window) tails spill at level 1; the pooled
    level-2 build must absorb them (uniform across shards) and keep the
    contraction exact."""
    n, d, k, n_dev = 512, 256, 8, 4
    cols, vals = _ell(rng, n, d, k)
    cols[:, :4] = np.arange(4)[None, :]       # 4 super-hot columns...
    per = n // n_dev
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        d, hot_threshold=10 ** 9,             # ...forced OFF the dense side
        overflow_threshold=4,
    )
    ovfs = [p.col_dir.overflow is not None for p in pairs]
    assert all(ovfs)                          # pooled level-2 built...
    for p in pairs:                           # ...and spill absorbed
        assert p.col_dir.n_spill == 0
    ref = build_grr_pair(cols, vals, d, hot_threshold=10 ** 9)
    r = rng.normal(0, 1, n).astype(np.float32)
    got = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
              for i, p in enumerate(pairs))
    np.testing.assert_allclose(got, _pair_tdot(ref, r), rtol=2e-4,
                               atol=5e-4)


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_shard_sparse_batch_grr_objective_equivalence(rng):
    """Assembled GRR-sharded batch through the psum objective == the
    single-device GRR objective (value, gradient, Hdiag, margins)."""
    from photon_ml_tpu.data.batch import make_sparse_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.parallel import (
        DistributedGLMObjective,
        data_parallel_mesh,
        shard_sparse_batch,
    )

    n, d, k = 512, 300, 6
    cols, vals = _ell(rng, n, d, k, hot_col=5)
    rows = [(cols[i], vals[i]) for i in range(n)]
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = rng.uniform(0.5, 1.5, n)

    mesh = data_parallel_mesh(8)
    sharded = shard_sparse_batch(rows, d, labels, mesh, weights=weights,
                                 layout="grr")
    assert sharded.grr is not None
    local = make_sparse_batch(rows, d, labels, weights=weights, grr=True)

    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=__import__(
            "photon_ml_tpu.ops.regularization",
            fromlist=["RegularizationContext"],
        ).RegularizationContext.l2(0.3),
        norm=NormalizationContext.identity(),
    )
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)
    w = jnp.asarray(rng.normal(0, 0.5, d).astype(np.float32))

    v1, g1 = obj.value_and_gradient(w, local)
    v8, g8 = dist.value_and_gradient(w, sharded)
    np.testing.assert_allclose(float(v8), float(v1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g8), np.asarray(g1),
                               rtol=2e-4, atol=5e-4)

    hd1 = obj.hessian_diagonal(w, local)
    hd8 = dist.hessian_diagonal(w, sharded)
    np.testing.assert_allclose(np.asarray(hd8), np.asarray(hd1),
                               rtol=2e-4, atol=5e-4)

    m1 = obj.predict_margins(w, local)
    m8 = dist.predict_margins(w, sharded)
    np.testing.assert_allclose(np.asarray(m8), np.asarray(m1),
                               rtol=2e-4, atol=5e-4)
    # raw scoring path (FixedEffectCoordinate.score contract)
    x1 = local.x_dot(w)
    x8 = dist.x_dot(w, sharded)
    np.testing.assert_allclose(np.asarray(x8), np.asarray(x1),
                               rtol=2e-4, atol=5e-4)


def test_sharded_mid_hot_columns(rng):
    """The sharded build routes mid-hot columns to per-shard compact
    plans with mesh-uniform structure; partial t_dots still sum to the
    global contraction."""
    n, k, dim, n_dev = 2048, 6, 1500, 4
    cols = np.zeros((n, k), np.int64)
    cols[:, 0] = rng.integers(0, 12, n)                # mid-hot band
    cols[:, 1:] = rng.integers(12, dim, (n, k - 1))
    for j in range(1, k):
        for _ in range(6):
            dup = (cols[:, j:j + 1] == cols[:, :j]).any(axis=1)
            if not dup.any():
                break
            cols[dup, j] = rng.integers(12, dim, int(dup.sum()))
    cols = cols.astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    per = n // n_dev
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        dim, hot_threshold=10 ** 9, mid_threshold=30,
    )
    assert all(p.col_mid is not None for p in pairs)
    shapes = {tuple(lf.shape for lf in jax.tree.leaves(p.col_mid))
              for p in pairs}
    assert len(shapes) == 1                   # mesh-uniform
    for p in pairs[1:]:
        np.testing.assert_array_equal(np.asarray(p.mid_ids),
                                      np.asarray(pairs[0].mid_ids))
    ref = build_grr_pair(cols, vals, dim, hot_threshold=10 ** 9,
                         mid_threshold=30)
    r = rng.normal(0, 1, n).astype(np.float32)
    got = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
              for i, p in enumerate(pairs))
    np.testing.assert_allclose(got, _pair_tdot(ref, r), rtol=2e-4,
                               atol=5e-4)
    w = rng.normal(0, 1, dim).astype(np.float32)
    got_m = np.concatenate([_pair_dot(p, w) for p in pairs])
    np.testing.assert_allclose(got_m, _pair_dot(ref, w), rtol=2e-4,
                               atol=5e-4)


def test_sharded_mid_cap_seeded_from_heaviest_shard(rng):
    """Mid mass concentrated AWAY from shard 0: the mid cap must come
    from a shard that carries mid entries, not shard 0's empty plan."""
    n, k, dim, n_dev = 2048, 4, 800, 4
    per = n // n_dev
    cols = rng.integers(10, dim, (n, k)).astype(np.int64)
    # Shards 1-3: column ids 0..15 appear densely; shard 0 never sees
    # them (per-(col, window) occupancy ~32 — mid class, under the 64
    # capacity ceiling).
    cols[per:, 0] = rng.integers(0, 16, n - per)
    for j in range(1, k):
        for _ in range(6):
            dup = (cols[:, j:j + 1] == cols[:, :j]).any(axis=1)
            if not dup.any():
                break
            cols[dup, j] = rng.integers(10, dim, int(dup.sum()))
    cols = cols.astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        dim, hot_threshold=10 ** 9, mid_threshold=64,
    )
    assert all(p.col_mid is not None for p in pairs)
    # Cap sized for the heavy shards' occupancy (~32 entries per mid
    # col per shard-window) — an empty-shard seed would give 4.
    assert pairs[0].col_mid.cap >= 32
    # At most start-lane fluctuation on the COO fallback (tiny 512-row
    # shards expose only 4 start rows); a bad cap seed spills ~90%.
    for p in pairs[1:]:
        m = int(np.count_nonzero(np.asarray(p.col_mid.spill_val)))
        assert m < 0.05 * 512, m
    ref = build_grr_pair(cols, vals, dim, hot_threshold=10 ** 9,
                         mid_threshold=64)
    r = rng.normal(0, 1, n).astype(np.float32)
    got = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
              for i, p in enumerate(pairs))
    np.testing.assert_allclose(got, _pair_tdot(ref, r), rtol=2e-4,
                               atol=5e-4)


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_sharded_pairs_multiwindow_zipf(rng):
    """Round-4 verdict weak #5: the sharded suite topped out below one
    table window per direction (d=600, per-shard rows=128), so the
    dense-grid multi-window machinery was never exercised on a mesh.
    Here BOTH directions span multiple windows per shard (d >= 2·WIN
    columns; per-shard rows > WIN) with zipf skew, so spill + pooled
    overflow are active per shard."""
    from photon_ml_tpu.data.grr import WIN

    n, d, k, n_dev = 8 * 20480, 40_000, 6, 8
    cols, vals = _ell(rng, n, d, k, skew=True)
    per = n // n_dev
    assert per > WIN and d > 2 * WIN   # the shapes this test exists for
    pairs = build_sharded_grr_pairs(
        [cols[i * per:(i + 1) * per] for i in range(n_dev)],
        [vals[i * per:(i + 1) * per] for i in range(n_dev)],
        d, overflow_threshold=256,
    )
    # Multi-window in both directions on every shard.
    assert pairs[0].row_dir.n_gw >= 2    # table = column space
    assert pairs[0].col_dir.n_gw >= 2    # table = shard row space
    ref = build_grr_pair(cols, vals, d, col_range_split=False)

    w = rng.normal(0, 1, d).astype(np.float32)
    r = rng.normal(0, 1, n).astype(np.float32)
    got = np.concatenate([_pair_dot(p, w) for p in pairs])
    np.testing.assert_allclose(got, _pair_dot(ref, w), rtol=2e-4,
                               atol=5e-4)
    got_g = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
                for i, p in enumerate(pairs))
    np.testing.assert_allclose(got_g, _pair_tdot(ref, r),
                               rtol=2e-4, atol=2e-3)
    # Congruence still holds at multi-window shapes.
    t0 = jax.tree.flatten(pairs[0])[1]
    s0 = [lf.shape for lf in jax.tree.leaves(pairs[0])]
    for p in pairs[1:]:
        leaves, tdef = jax.tree.flatten(p)
        assert tdef == t0
        assert [lf.shape for lf in leaves] == s0


@pytest.mark.slow   # 10s+ in tests/tier1_durations.json
def test_sharded_pairs_col_range_split(rng):
    """Round-5: the column-range split engages on sharded builds too —
    same ranges on every shard (pooled sample), per-range caps common,
    overflow pooled and padded per range — and reproduces the global
    plan's contraction."""
    from photon_ml_tpu.data.grr import WIN, GrrRangeSplit

    n, d, k, n_dev = 8 * WIN, 70_000, 16, 8
    x0 = 5000.0
    u = rng.uniform(size=(n, k))
    cols = np.minimum(x0 * np.exp(u * np.log((d + x0) / x0)) - x0,
                      d - 1).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    per = n // n_dev
    shard_c = [cols[i * per:(i + 1) * per] for i in range(n_dev)]
    shard_v = [vals[i * per:(i + 1) * per] for i in range(n_dev)]
    pairs = build_sharded_grr_pairs(shard_c, shard_v, d)
    assert isinstance(pairs[0].row_dir, GrrRangeSplit)
    bounds = pairs[0].row_dir.bounds
    for p in pairs[1:]:
        assert p.row_dir.bounds == bounds          # same ranges everywhere
    caps0 = [q.cap for q in pairs[0].row_dir.parts]
    for p in pairs[1:]:
        assert [q.cap for q in p.row_dir.parts] == caps0
    assert len(set(caps0)) >= 2                    # ranges chose own caps

    unsplit = build_sharded_grr_pairs(shard_c, shard_v, d,
                                      col_range_split=False)
    s = pairs[0].row_dir.plan_stats()
    su = unsplit[0].row_dir.plan_stats()
    assert s["spill_frac"] < su["spill_frac"] / 3

    ref = build_grr_pair(cols, vals, d, col_range_split=False)
    w = rng.normal(0, 1, d).astype(np.float32)
    got = np.concatenate([_pair_dot(p, w) for p in pairs])
    np.testing.assert_allclose(got, _pair_dot(ref, w), rtol=2e-4,
                               atol=5e-4)
    r = rng.normal(0, 1, n).astype(np.float32)
    got_g = sum(_pair_tdot(p, r[i * per:(i + 1) * per])
                for i, p in enumerate(pairs))
    np.testing.assert_allclose(got_g, _pair_tdot(ref, r), rtol=2e-4,
                               atol=2e-3)
    t0 = jax.tree.flatten(pairs[0])[1]
    s0 = [lf.shape for lf in jax.tree.leaves(pairs[0])]
    for p in pairs[1:]:
        leaves, tdef = jax.tree.flatten(p)
        assert tdef == t0
        assert [lf.shape for lf in leaves] == s0


@pytest.mark.parametrize("without", ["the_two_entries", "the_library"])
def test_sharded_pairs_leaves_equal_native_hot_split_or_numpy_body(
        rng, monkeypatch, hot_split, without):
    """Four shards with all three column classes: every shard's count
    and class split through the native library (ISSUE 40: the mesh
    build runs the resident build's two functions per shard) against
    ``np.bincount`` and the numpy body, with the other native builders
    in place and with ``PHOTON_ML_TPU_NATIVE=0``: every leaf of every
    shard's pair equal, and one sha256 over the list."""
    from photon_ml_tpu.data import grr

    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)
    n_shards, per, d, k = 4, 3000, 20000, 8
    cols, vals = _ell(rng, n_shards * per, d, k, hot_col=7, skew=True)
    vals[rng.random(vals.shape) < 0.05] = 0.0

    def build():
        return build_sharded_grr_pairs(
            [cols[i * per:(i + 1) * per] for i in range(n_shards)],
            [vals[i * per:(i + 1) * per] for i in range(n_shards)], d)

    native = build()
    assert hot_split.calls == ["count"] * n_shards + ["split"] * n_shards
    for pair in native:
        assert pair.hot_ids.size and pair.planned_ids.size
        assert pair.tail.nnz
    del hot_split.calls[:]
    hot_split.without(without)
    numpy = build()
    assert not hot_split.calls
    hot_split.same_bytes(native, numpy)
