"""Out-of-core chunk store: spilled ≡ resident, RSS bounded, safe.

Round-8 tentpole (ISSUE 3): chunks spill to atomic content-keyed
``.npz`` files (``data/chunk_store.py``) with an LRU host window and a
background disk→host→device prefetch thread in ``optim.streaming``.
The contracts under test:

- round-trip equality — a spilled sweep reproduces the RAM-resident
  chunked path to float tolerance on value/grad/HVP/Hessian-diagonal,
  margins, the swept-λ surface, the streaming solver, the estimator,
  and composed with the 8-device mesh;
- the LRU bound holds (live decoded chunks never exceed
  ``host_max_resident``) and the chunk visit order stays deterministic
  under prefetch (the float-summation-order parity guarantee);
- corrupt or missing chunk files fall back to a lineage rebuild (and
  re-spill) — the store can never fail a run;
- spilled files are a warm-ETL artifact (same content key ⇒ rebuild
  skipped);
- ``invalidate()`` quiesces the prefetch pipeline before buffers are
  freed (no use-after-evict), stress-tested interleaved with sweeps.
"""

from __future__ import annotations

import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import make_sparse_batch
from photon_ml_tpu.data.chunked_batch import build_chunked_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.data.sparse_rows import SparseRows
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import (
    RegularizationContext,
    RegularizationType,
    SweptRegularization,
)
from photon_ml_tpu.optim.base import OptimizerConfig
from photon_ml_tpu.optim.streaming import (
    ChunkedGLMObjective,
    streaming_lbfgs_solve,
)


@pytest.fixture
def rng():
    return np.random.default_rng(23)


def _sparse_problem(rng, n=2000, d=900, k=8):
    cols = np.stack([
        np.sort(rng.choice(d, k, replace=False)) for _ in range(n)
    ]).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = rng.uniform(0.5, 1.5, n).astype(np.float32)
    offsets = rng.normal(0, 0.1, n).astype(np.float32)
    indptr = np.arange(n + 1, dtype=np.int64) * k
    rows = SparseRows.from_flat(indptr, cols.reshape(-1).astype(np.int64),
                                vals.reshape(-1))
    return rows, labels, weights, offsets


def _objective(reg=None):
    return GLMObjective(
        loss=losses.LOGISTIC,
        reg=reg if reg is not None else RegularizationContext.l2(0.7),
        norm=NormalizationContext.identity(),
    )


def _spilled(rng, tmp_path, layout="ell", n_chunks=6, window=2, depth=2,
             mesh=None, **prob_kw):
    rows, labels, weights, offsets = _sparse_problem(rng, **prob_kw)
    cb = build_chunked_batch(
        rows, 900, labels, weights=weights, offsets=offsets,
        n_chunks=n_chunks, layout=layout, mesh=mesh,
        spill_dir=str(tmp_path / "spill"), host_max_resident=window)
    cobj = ChunkedGLMObjective(_objective(), cb, max_resident=0,
                               prefetch_depth=depth)
    return rows, labels, weights, offsets, cb, cobj


@pytest.mark.parametrize("layout", ["ell", "grr"])
def test_spilled_matches_resident(rng, tmp_path, layout):
    """Spilled sweep ≡ resident batch on every objective surface."""
    rows, labels, weights, offsets, cb, cobj = _spilled(
        rng, tmp_path, layout=layout)
    assert cb.store is not None and cb.store.spills == cb.n_chunks
    resident = make_sparse_batch(rows, 900, labels, weights=weights,
                                 offsets=offsets)
    obj = _objective()
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, 900), jnp.float32)

    f_r, g_r = obj.value_and_gradient(w, resident)
    f_c, g_c = cobj.value_and_gradient(w)
    np.testing.assert_allclose(float(f_c), float(f_r), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(cobj.value(w)),
                               float(obj.value(w, resident)), rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(cobj.hessian_vector(w, v)),
        np.asarray(obj.hessian_vector(w, v, resident)),
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(cobj.hessian_diagonal(w)),
        np.asarray(obj.hessian_diagonal(w, resident)),
        rtol=2e-4, atol=2e-4)
    # _per_example sweeps run the same prefetch pipeline.
    np.testing.assert_allclose(
        cobj.predict_margins(w),
        np.asarray(obj.predict_margins(w, resident)),
        rtol=2e-4, atol=2e-4)


def test_spilled_swept_lanes_match_resident_chunked(rng, tmp_path):
    """Swept-λ surface: spilled lanes ≡ resident chunked lanes (the
    batched grid path composes with the disk tier)."""
    rows, labels, weights, offsets, cb, cobj = _spilled(rng, tmp_path)
    reg = SweptRegularization.from_grid(RegularizationType.L2,
                                        [3.0, 0.7, 0.05])
    cb_res = build_chunked_batch(rows, 900, labels, weights=weights,
                                 offsets=offsets, n_chunks=6,
                                 layout="ell")
    co_res = ChunkedGLMObjective(_objective(), cb_res, max_resident=6)
    W = jnp.asarray(rng.normal(0, 0.2, (3, 900)), jnp.float32)
    F_r, G_r = co_res.value_and_gradient_swept(W, reg)
    F_s, G_s = cobj.value_and_gradient_swept(W, reg)
    np.testing.assert_allclose(np.asarray(F_s), np.asarray(F_r),
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(G_s), np.asarray(G_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cobj.value_swept(W, reg)),
                               np.asarray(co_res.value_swept(W, reg)),
                               rtol=2e-5)


def test_streaming_solver_spilled_matches_ram_resident(rng, tmp_path):
    """The full host-driven solve over the disk tier lands on the same
    optimum as the all-in-RAM chunked solve (chunk visit order and
    accumulation order are identical, so this is tight)."""
    rows, labels, weights, offsets, cb, cobj = _spilled(rng, tmp_path)
    cb_res = build_chunked_batch(rows, 900, labels, weights=weights,
                                 offsets=offsets, n_chunks=6,
                                 layout="ell")
    co_res = ChunkedGLMObjective(_objective(), cb_res, max_resident=6)
    cfg = OptimizerConfig(max_iters=40, tolerance=1e-5)
    w0 = jnp.zeros((900,), jnp.float32)
    res_r = streaming_lbfgs_solve(co_res.value_and_gradient, w0, cfg,
                                  value_fn=co_res.value)
    res_s = streaming_lbfgs_solve(cobj.value_and_gradient, w0, cfg,
                                  value_fn=cobj.value)
    np.testing.assert_allclose(float(res_s.value), float(res_r.value),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(res_s.w), np.asarray(res_r.w),
                               rtol=1e-3, atol=1e-3)
    assert cobj.sweeps == co_res.sweeps   # odometer parity


def test_lru_bound_and_deterministic_order(rng, tmp_path):
    """Live decoded chunks never exceed ``host_max_resident`` (the RSS
    proxy), and the store sees chunks in exactly the sweep order,
    sweep after sweep, despite the prefetch thread."""
    rows, labels, weights, offsets, cb, cobj = _spilled(
        rng, tmp_path, n_chunks=8, window=2, depth=3)
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    for _ in range(3):
        cobj.value_and_gradient(w)
    assert cb.store.peak_resident <= 2
    assert cb.store.n_resident <= 2
    assert cb.store.access_log == list(range(8)) * 3
    assert cb.store.rebuilds == 0


def test_corrupt_and_missing_chunk_fall_back_to_rebuild(rng, tmp_path):
    """A truncated or deleted chunk file degrades to a lineage rebuild
    (+ re-spill), never to a failure — plan-cache discipline."""
    rows, labels, weights, offsets, cb, cobj = _spilled(rng, tmp_path)
    resident = make_sparse_batch(rows, 900, labels, weights=weights,
                                 offsets=offsets)
    obj = _objective()
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    f_r = float(obj.value(w, resident))

    with open(cb.store.path(3), "wb") as f:
        f.write(b"not a zip")
    os.remove(cb.store.path(5))
    np.testing.assert_allclose(float(cobj.value(w)), f_r, rtol=2e-5)
    assert cb.store.rebuilds == 2
    # The fallback re-spilled both: the next sweep reads clean files.
    np.testing.assert_allclose(float(cobj.value(w)), f_r, rtol=2e-5)
    assert cb.store.rebuilds == 2


def test_spilled_store_is_warm_etl_artifact(rng, tmp_path):
    """Rebuilding the same dataset against the same spill_dir writes
    nothing: the content-keyed files double as a persistent warm-ETL
    cache, and the warm batch still sweeps correctly."""
    rows, labels, weights, offsets, cb, cobj = _spilled(rng, tmp_path)
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    f1 = float(cobj.value(w))
    mtimes = {i: os.path.getmtime(cb.store.path(i))
              for i in range(cb.n_chunks)}

    cb2 = build_chunked_batch(
        rows, 900, labels, weights=weights, offsets=offsets,
        n_chunks=6, layout="ell", spill_dir=str(tmp_path / "spill"),
        host_max_resident=2)
    assert cb2.store.spills == 0          # nothing rebuilt
    for i in range(cb2.n_chunks):
        assert os.path.getmtime(cb2.store.path(i)) == mtimes[i]
    cobj2 = ChunkedGLMObjective(_objective(), cb2, max_resident=0)
    np.testing.assert_allclose(float(cobj2.value(w)), f1, rtol=1e-6)

    # Different content (weights perturbed) keys a DIFFERENT store —
    # never a silent stale hit.
    cb3 = build_chunked_batch(
        rows, 900, labels, weights=weights * 2.0, offsets=offsets,
        n_chunks=6, layout="ell", spill_dir=str(tmp_path / "spill"),
        host_max_resident=2)
    assert cb3.store.key != cb2.store.key
    assert cb3.store.spills == cb3.n_chunks


def test_set_offsets_external_to_spilled_payload(rng, tmp_path):
    """``set_offsets`` must not rewrite chunk files (offsets are CD
    state, overlaid at access time) and the next sweep must see the
    new offsets."""
    rows, labels, weights, offsets, cb, cobj = _spilled(rng, tmp_path)
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    cobj.value(w)
    mtimes = [os.path.getmtime(cb.store.path(i))
              for i in range(cb.n_chunks)]
    new_off = rng.normal(0, 0.3, cb.n).astype(np.float32)
    cb.set_offsets(new_off)
    cobj.invalidate()
    resident = make_sparse_batch(rows, 900, labels, weights=weights,
                                 offsets=new_off)
    np.testing.assert_allclose(
        float(cobj.value(w)), float(_objective().value(w, resident)),
        rtol=2e-5)
    assert [os.path.getmtime(cb.store.path(i))
            for i in range(cb.n_chunks)] == mtimes


def test_invalidate_interleaved_with_sweeps_stress(rng, tmp_path):
    """Satellite: invalidate() quiesces the prefetch thread before
    anything is freed.  Interleave sweeps, offset updates, and
    invalidations across every surface; thread count must return to
    baseline (no leaked prefetchers) and values stay exact."""
    rows, labels, weights, offsets, cb, cobj = _spilled(
        rng, tmp_path, n_chunks=8, window=1, depth=3, n=1600)
    obj = _objective()
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    base_threads = threading.active_count()
    for step in range(6):
        off = rng.normal(0, 0.2, cb.n).astype(np.float32)
        cb.set_offsets(off)
        cobj.invalidate()
        resident = make_sparse_batch(rows, 900, labels,
                                     weights=weights, offsets=off)
        np.testing.assert_allclose(float(cobj.value(w)),
                                   float(obj.value(w, resident)),
                                   rtol=2e-5)
        if step % 2:
            cobj.predict_margins(w)   # _per_example pipeline too
        cobj.invalidate()             # idempotent, quiesced
    assert threading.active_count() <= base_threads + 1
    cb.store.assert_quiesced()        # no reader left behind
    cb.store.drop_resident()          # legal only when quiesced
    assert cb.store.n_resident == 0


def test_store_asserts_on_unquiesced_free():
    """Freeing the window under an active reader is a loud error."""
    from photon_ml_tpu.data.chunk_store import ChunkStore

    store = ChunkStore("/tmp/unused", "k", 1, host_max_resident=1)
    store.begin_read()
    with pytest.raises(RuntimeError, match="quiesce"):
        store.drop_resident()
    store.end_read()
    store.drop_resident()


def test_spilled_mesh_composes(rng, tmp_path):
    """chunks × shards × disk: spilled chunks assembled example-sharded
    on the 8-device mesh equal the resident batch."""
    from photon_ml_tpu.parallel.mesh import data_parallel_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    mesh = data_parallel_mesh(8)
    rows, labels, weights, offsets, cb, cobj = _spilled(
        rng, tmp_path, n_chunks=2, window=1, mesh=mesh)
    resident = make_sparse_batch(rows, 900, labels, weights=weights,
                                 offsets=offsets)
    obj = _objective()
    w = jnp.asarray(rng.normal(0, 0.2, 900), jnp.float32)
    f_r, g_r = obj.value_and_gradient(w, resident)
    f_c, g_c = cobj.value_and_gradient(w)
    np.testing.assert_allclose(float(f_c), float(f_r), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        cobj.x_dot(w), np.asarray(resident.x_dot(w))[: cb.n],
        rtol=2e-4, atol=2e-4)


def test_estimator_spilled_fit_matches_resident(rng, tmp_path):
    """GameEstimator with spill_dir ≡ the RAM-resident chunked fit,
    through CD + swept-λ grid training and transformer scoring."""
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.game.dataset import GameDataset
    from photon_ml_tpu.models.glm import TaskType

    n, d, k = 800, 100, 5
    cols = np.stack([
        np.sort(rng.choice(d, k, replace=False)) for _ in range(n)
    ]).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    w_true = rng.normal(0, 1, d)
    m = np.einsum("nk,nk->n", vals, w_true[cols])
    y = (m + rng.normal(0, 0.3, n) > 0).astype(np.float32)
    rows = [(cols[i], vals[i]) for i in range(n)]
    ds = GameDataset(labels=y, features={"f": rows}, entity_ids={},
                     feature_dims={"f": d})

    def cfg(**kw):
        return TrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinates=[CoordinateConfig(
                name="global", kind=CoordinateKind.FIXED_EFFECT,
                feature_shard="f",
                optimizer=OptimizerSettings(max_iters=40,
                                            reg_weight=1.0))],
            update_sequence=["global"], n_iterations=1,
            reg_weight_grid={"global": [2.0, 0.5]},
            validation_fraction=0.0, validate_per_iteration=False,
            intercept=False, chunk_rows=192, chunk_layout="ELL", **kw)

    fits_r = GameEstimator(cfg(chunk_max_resident=8)).fit(ds)
    fits_s = GameEstimator(cfg(
        spill_dir=str(tmp_path / "est_spill"), host_max_resident=1,
        prefetch_depth=2, chunk_max_resident=0)).fit(ds)
    assert len(fits_s) == len(fits_r) == 2
    for fr, fs in zip(fits_r, fits_s):
        w_r = np.asarray(fr.model.models["global"].coefficients.means)
        w_s = np.asarray(fs.model.models["global"].coefficients.means)
        np.testing.assert_allclose(w_s, w_r, rtol=5e-3, atol=5e-3)
    spill_root = tmp_path / "est_spill" / "chunks"
    assert spill_root.is_dir() and any(spill_root.iterdir())


@pytest.mark.fast
def test_spill_config_validation():
    from photon_ml_tpu.config import (
        CoordinateConfig,
        CoordinateKind,
        OptimizerSettings,
        TrainingConfig,
    )
    from photon_ml_tpu.models.glm import TaskType

    base = dict(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinates=[CoordinateConfig(
            name="g", kind=CoordinateKind.FIXED_EFFECT,
            feature_shard="f", optimizer=OptimizerSettings())],
        update_sequence=["g"],
    )
    with pytest.raises(ValueError, match="spill_dir"):
        TrainingConfig(spill_dir="/tmp/s", **base).validate()
    with pytest.raises(ValueError, match="host_max_resident"):
        TrainingConfig(chunk_rows=100, spill_dir="/tmp/s",
                       host_max_resident=0, **base).validate()
    with pytest.raises(ValueError, match="prefetch_depth"):
        TrainingConfig(chunk_rows=100, prefetch_depth=-1,
                       **base).validate()
    TrainingConfig(chunk_rows=100, spill_dir="/tmp/s",
                   host_max_resident=2, prefetch_depth=0,
                   **base).validate()


def test_env_spill_default_applies_at_config_layer_only(
        rng, tmp_path, monkeypatch):
    """$PHOTON_ML_TPU_SPILL_DIR must flow through the config/estimator
    layer and NEVER flip a direct `build_chunked_batch` caller to the
    spill store — parity baselines and a comparison's control side build
    resident batches through that API (review finding: an ambient env
    var silently turned the resident arm into spilled-vs-spilled)."""
    from photon_ml_tpu.data.chunk_store import resolve_spill_dir

    rows, labels, weights, offsets = _sparse_problem(rng, n=400, d=50,
                                                     k=4)
    monkeypatch.setenv("PHOTON_ML_TPU_SPILL_DIR",
                       str(tmp_path / "env_spill"))
    cb = build_chunked_batch(rows, 50, labels, n_chunks=2, layout="ell")
    assert cb.store is None                      # library API: explicit
    assert not (tmp_path / "env_spill").exists()
    assert resolve_spill_dir(None) == str(tmp_path / "env_spill")
    cb2 = build_chunked_batch(rows, 50, labels, n_chunks=2,
                              layout="ell",
                              spill_dir=resolve_spill_dir(None))
    assert cb2.store is not None                 # config-layer route


def test_grr_store_key_tracks_planner_version(rng, tmp_path,
                                              monkeypatch):
    """GRR chunk files embed compiled plans: a PLANNER_VERSION bump
    must orphan them (clean rebuild), exactly like plan-cache entries
    (review finding: stale plans would warm-load into new kernels)."""
    import photon_ml_tpu.data.grr as grr_mod
    from photon_ml_tpu.data.chunk_store import store_key

    rows, labels, weights, offsets = _sparse_problem(rng, n=400, d=50,
                                                     k=4)
    kw = dict(dim=50, chunk_rows=200, n_dev=1, row_capacity=4)
    k1 = store_key(rows, labels, weights, layout="grr", **kw)
    # drop_ell_with_grr changes the payload, so it changes the key.
    assert store_key(rows, labels, weights, layout="grr",
                     drop_ell_with_grr=False, **kw) != k1
    k_ell = store_key(rows, labels, weights, layout="ell", **kw)
    monkeypatch.setattr(grr_mod, "PLANNER_VERSION",
                        grr_mod.PLANNER_VERSION + 1)
    assert store_key(rows, labels, weights, layout="grr", **kw) != k1
    # ELL payloads embed no plans: planner version is not in their key.
    assert store_key(rows, labels, weights, layout="ell", **kw) == k_ell


@pytest.mark.fast
def test_mmap_npz_roundtrip(tmp_path):
    """The zip-member mmap reader returns exactly what was saved, as
    file-backed views (no anonymous copy)."""
    from photon_ml_tpu.cache.plan_cache import atomic_savez
    from photon_ml_tpu.data.chunk_store import _open_npz_mmap

    arrays = {
        "a": np.arange(1000, dtype=np.int32).reshape(50, 20),
        "b": np.linspace(0, 1, 37, dtype=np.float32),
        "c": np.zeros(0, np.float32),
    }
    path = str(tmp_path / "x" / "t.npz")
    atomic_savez(path, {"hello": 1}, arrays)
    out = _open_npz_mmap(path)
    for name, a in arrays.items():
        got = out[name]
        assert isinstance(got, np.memmap)
        np.testing.assert_array_equal(np.asarray(got), a)
    import json

    assert json.loads(bytes(np.asarray(out["__meta__"])))["hello"] == 1


@pytest.mark.fast
def test_prefetcher_error_delivered_in_band():
    """A producer-thread failure surfaces at the consumer's ``next()``
    as the original exception.  Since ISSUE 6 the error RIDES THE QUEUE
    (sentinel item) instead of a shared attribute — the lint
    unlocked-shared-write fix — so delivery needs no lock and cannot
    race the consumer."""
    from photon_ml_tpu.optim.streaming import ChunkPrefetcher

    def load(i):
        if i == 2:
            raise OSError("disk went away")
        return np.full(4, i, np.float32)

    pf = ChunkPrefetcher(load, lambda h: h, depth=2)
    pf.start(range(4))
    try:
        assert pf.next(0)[0] == 0
        assert pf.next(1)[0] == 1
        with pytest.raises(OSError, match="disk went away"):
            pf.next(2)
    finally:
        pf.close()


@pytest.mark.fast
def test_prefetch_stream_error_and_cleanup(tmp_path):
    """Same contract through the generator wrapper: the error raises at
    the failing chunk and the store reader count still drains to zero
    (quiescence is structural)."""
    from photon_ml_tpu.data.chunk_store import ChunkStore
    from photon_ml_tpu.optim.streaming import prefetch_stream

    store = ChunkStore(str(tmp_path), "k", n_chunks=3)

    def load(i):
        if i == 1:
            raise ValueError("bad chunk")
        return i

    with pytest.raises(ValueError, match="bad chunk"):
        for _i, _c in prefetch_stream(load, lambda h: h, range(3),
                                      depth=2, store=store):
            pass
    store.assert_quiesced()   # reader released despite the error
