"""Plan cache (photon_ml_tpu.cache): round-trip equality, keyed
invalidation, and corruption fallback.

The warm path must be bit-compatible with the cold path (a cached plan
contracts identically to a fresh build) and must NEVER be able to make
a run fail — every bad-entry mode degrades to a rebuild.
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.cache import plan_cache
from photon_ml_tpu.data import grr as grr_mod
from photon_ml_tpu.data.grr import (
    build_grr_pair,
    build_sharded_grr_pairs,
)


@pytest.fixture
def rng():
    return np.random.default_rng(17)


def _ell(rng, n=3000, d=1200, k=6):
    cols = np.stack([
        np.sort(rng.choice(d, k, replace=False)) for _ in range(n)
    ]).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    return cols, vals, d


def _named(spans, name):
    return [e for e in spans if e["name"] == name]


def _cache_hit(spans):
    """``cache_hit`` of the one ``grr_plan_build`` among ``spans``."""
    [build] = _named(spans, "grr_plan_build")
    return build["args"]["cache_hit"]


def _contract_both(pair, rng, n, d):
    w = rng.normal(0, 1, d).astype(np.float32)
    r = rng.normal(0, 1, n).astype(np.float32)
    return np.asarray(pair.dot(w)), np.asarray(pair.t_dot(r))


@pytest.mark.fast
def test_cache_round_trip_contraction_equality(rng, tmp_path, spans_of):
    """Second build of identical inputs is a hit, and the cached plan's
    contractions equal the fresh build's in both directions."""
    cols, vals, d = _ell(rng)
    td = str(tmp_path)
    fresh, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
    assert _cache_hit(spans) == 0
    assert len(_named(spans, "plan_cache_save")) == 1
    dot_f, tdot_f = _contract_both(fresh, np.random.default_rng(5),
                                   cols.shape[0], d)

    cached, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
    assert _cache_hit(spans) == 1
    [load] = _named(spans, "plan_cache_load")
    assert load["args"]["bytes"] > 0
    assert not _named(spans, "plan_cache_save")
    dot_c, tdot_c = _contract_both(cached, np.random.default_rng(5),
                                   cols.shape[0], d)
    np.testing.assert_allclose(dot_c, dot_f, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(tdot_c, tdot_f, rtol=1e-4, atol=1e-4)


@pytest.mark.fast
def test_cache_invalidation_on_data_config_version(rng, tmp_path, spans_of):
    """Any of (data bytes, plan options, planner version) changing is a
    clean miss — never a stale hit."""
    td = str(tmp_path)
    cols, vals, d = _ell(rng)
    build_grr_pair(cols, vals, d, cache_dir=td)

    # Data change: one value flips -> different fingerprint.
    vals2 = vals.copy()
    vals2[0, 0] += 1.0
    _, spans = spans_of(build_grr_pair, cols, vals2, d, cache_dir=td)
    assert _cache_hit(spans) == 0

    # Config change: explicit cap -> different config key.
    _, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td, cap=8)
    assert _cache_hit(spans) == 0

    # Version change: a planner bump orphans every old entry.
    old = grr_mod.PLANNER_VERSION
    grr_mod.PLANNER_VERSION = old + 1
    try:
        _, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
        assert _cache_hit(spans) == 0
    finally:
        grr_mod.PLANNER_VERSION = old

    # Unchanged inputs still hit.
    _, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
    assert _cache_hit(spans) == 1


@pytest.mark.fast
def test_corrupt_cache_entry_falls_back_to_rebuild(rng, tmp_path, spans_of):
    """Truncated or garbage entries are rebuilt (and the rebuild
    overwrites them), never crash."""
    td = str(tmp_path)
    cols, vals, d = _ell(rng, n=1500)
    build_grr_pair(cols, vals, d, cache_dir=td)
    plans_dir = os.path.join(td, "plans")
    [entry] = os.listdir(plans_dir)
    path = os.path.join(plans_dir, entry)

    # Truncate to half: a partial write a crash could have left behind
    # (the atomic rename makes this near-impossible, but readers must
    # survive it anyway).
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    assert plan_cache.load_plan(path) is None
    pair, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
    assert _cache_hit(spans) == 0
    assert pair.row_dir.n_segments == cols.shape[0]

    # Pure garbage (not even a zip).
    with open(path, "wb") as f:
        f.write(b"not a plan at all")
    assert plan_cache.load_plan(path) is None
    build_grr_pair(cols, vals, d, cache_dir=td)
    # The rebuild re-saved a good entry; next read hits.
    _, spans = spans_of(build_grr_pair, cols, vals, d, cache_dir=td)
    assert _cache_hit(spans) == 1


@pytest.mark.fast
def test_sharded_cache_round_trip(rng, tmp_path):
    """The sharded builder's congruent pair list round-trips as one
    entry with host leaves and per-shard contraction equality."""
    td = str(tmp_path)
    d = 800
    shard_cols, shard_vals = [], []
    for _ in range(2):
        c, v, _ = _ell(rng, n=1024, d=d, k=5)
        shard_cols.append(c)
        shard_vals.append(v)
    fresh = build_sharded_grr_pairs(shard_cols, shard_vals, d,
                                    cache_dir=td)
    cached = build_sharded_grr_pairs(shard_cols, shard_vals, d,
                                     cache_dir=td)
    assert len(cached) == len(fresh) == 2
    w = rng.normal(0, 1, d).astype(np.float32)
    for a, b in zip(fresh, cached):
        np.testing.assert_allclose(np.asarray(b.dot(w)),
                                   np.asarray(a.dot(w)),
                                   rtol=1e-5, atol=1e-5)
    # Host leaves preserved (the mesh assembly contract).
    leaf = (cached[0].col_dir.vals if not hasattr(
        cached[0].col_dir, "parts") else cached[0].col_dir.parts[0].vals)
    assert isinstance(leaf, np.ndarray)

    # Different shard count = different key.
    build_sharded_grr_pairs(shard_cols + shard_cols,
                            shard_vals + shard_vals, d, cache_dir=td)
    assert len(os.listdir(os.path.join(td, "plans"))) == 2


@pytest.mark.fast
def test_chunked_batch_uses_plan_cache(rng, tmp_path):
    """build_chunked_batch(cache_dir=...) round-trips its chunk plans:
    the second build hits (one plans/ entry) and evaluates
    identically."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.data.sparse_rows import SparseRows
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import ChunkedGLMObjective

    td = str(tmp_path)
    n, d, k = 2048, 600, 5
    cols, vals, _ = _ell(rng, n=n, d=d, k=k)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    rows = SparseRows.from_flat(
        np.arange(n + 1, dtype=np.int64) * k,
        cols.reshape(-1).astype(np.int64), vals.reshape(-1))
    obj = GLMObjective(loss=losses.LOGISTIC,
                       reg=RegularizationContext.l2(1.0),
                       norm=NormalizationContext.identity())
    w = jnp.asarray(rng.normal(0, 0.2, d), jnp.float32)

    cb1 = build_chunked_batch(rows, d, labels, n_chunks=2, layout="grr",
                              cache_dir=td)
    v1, g1 = ChunkedGLMObjective(obj, cb1).value_and_gradient(w)
    assert len(os.listdir(os.path.join(td, "plans"))) == 1
    cb2 = build_chunked_batch(rows, d, labels, n_chunks=2, layout="grr",
                              cache_dir=td)
    v2, g2 = ChunkedGLMObjective(obj, cb2).value_and_gradient(w)
    assert len(os.listdir(os.path.join(td, "plans"))) == 1
    np.testing.assert_allclose(float(v2), float(v1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                               rtol=1e-5, atol=1e-5)
