"""GRR layout + kernel tests (CPU: jnp plan execution + interpret kernel).

The GRR plan is validated semantically: executing the compiled plan must
reproduce the direct COO contraction exactly (same products, reordered
sums only), for random matrices across shapes, skews, spills, and hot
columns — plus the crossbar router invariants the advisor asked for.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.data.grr import (
    GrrPair,
    build_grr_direction,
    build_grr_pair,
    _split_classes,
    classify_columns,
)
from photon_ml_tpu.ops.crossbar import apply_route_numpy, route_tile


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _coo(rng, nnz, L, S):
    idx = rng.integers(0, L, nnz)
    seg = rng.integers(0, S, nnz)
    val = rng.normal(0, 1, nnz).astype(np.float32)
    return idx, seg, val


def _direct(idx, seg, val, table, S):
    out = np.zeros(S, np.float64)
    np.add.at(out, seg, val.astype(np.float64) * table[idx])
    return out.astype(np.float32)


@pytest.mark.parametrize("nnz,L,S,cap", [
    (2000, 300, 150, None),       # single window both sides
    (5000, 40000, 5000, 4),       # multiple gather windows
    (5000, 5000, 40000, 8),       # multiple segment windows
    (30000, 70000, 70000, None),  # multiple both
    (64, 17000, 17, 4),           # nearly empty blocks + dummy ows
])
def test_direction_matches_direct(rng, nnz, L, S, cap):
    idx, seg, val = _coo(rng, nnz, L, S)
    d = build_grr_direction(idx, seg, val, L, S, cap=cap)
    table = rng.normal(0, 1, L).astype(np.float32)
    out = np.asarray(d.contract(jnp.asarray(table)))
    want = _direct(idx, seg, val, table, S)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-4)


def test_direction_spill_overflow(rng):
    # One segment with far more entries in one window than cap → spill.
    L, S = 1000, 64
    idx = rng.integers(0, 128, 600)          # all in window 0
    seg = np.zeros(600, np.int64)            # all in segment 0
    val = rng.normal(0, 1, 600).astype(np.float32)
    d = build_grr_direction(idx, seg, val, L, S, cap=4)
    assert d.n_spill > 0
    table = rng.normal(0, 1, L).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(d.contract(jnp.asarray(table))),
        _direct(idx, seg, val, table, S), rtol=2e-5, atol=2e-4,
    )


def test_direction_duplicate_entries(rng):
    # Repeated (idx, seg) pairs must sum, not overwrite.
    idx = np.array([5, 5, 5, 7], np.int64)
    seg = np.array([1, 1, 2, 2], np.int64)
    val = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    d = build_grr_direction(idx, seg, val, 10, 4, cap=4)
    table = np.arange(10, dtype=np.float32)
    np.testing.assert_allclose(
        np.asarray(d.contract(jnp.asarray(table))),
        _direct(idx, seg, val, table, 4), rtol=1e-6,
    )


def test_direction_empty(rng):
    d = build_grr_direction(
        np.empty(0, np.int64), np.empty(0, np.int64),
        np.empty(0, np.float32), 100, 50,
    )
    out = np.asarray(d.contract(jnp.zeros(100)))
    assert out.shape == (50,)
    assert np.all(out == 0)


def test_squared_direction(rng):
    idx, seg, val = _coo(rng, 3000, 2000, 1500)
    d = build_grr_direction(idx, seg, val, 2000, 1500)
    table = rng.normal(0, 1, 2000).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(d.squared().contract(jnp.asarray(table))),
        _direct(idx, seg, val * val, table, 1500), rtol=2e-5, atol=2e-4,
    )


# -- hot split ---------------------------------------------------------------

def test_dense_hot_split(rng):
    n, k, dim = 512, 6, 300
    cols = rng.integers(1, dim, (n, k)).astype(np.int32)
    cols[:, 0] = 0                             # column 0 in every row → hot
    # make per-row cols unique to mirror SparseBatch's contract
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    counts = np.bincount(cols[vals != 0], minlength=dim)
    classes = classify_columns(counts, 1, hot_threshold=max(64, n // 16),
                               max_hot=128)
    x_hot, (kept_cols, kept_vals, width), tail = _split_classes(
        cols, vals, dim, n, classes)
    assert 0 in classes.hot and tail is None
    assert kept_cols is cols and width == dim   # no remap: one window
    assert x_hot.shape == (n, len(classes.hot))
    # hot entries are dropped from the sparse side
    assert not kept_vals[:, 0].any()
    # dense + sparse together reproduce every nonzero exactly once
    total_dense = x_hot.sum()
    total_sparse = kept_vals.sum()
    np.testing.assert_allclose(total_dense + total_sparse,
                               vals[vals != 0].sum(), rtol=1e-4)


def test_pair_matches_dense(rng):
    n, k, dim = 700, 8, 900
    cols = np.stack([rng.choice(dim, k, replace=False) for _ in range(n)])
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    cols[:, 0] = 0                             # hot column
    pair = build_grr_pair(cols, vals, dim)

    x = np.zeros((n, dim), np.float64)
    np.add.at(x, (np.repeat(np.arange(n), k), cols.reshape(-1)),
              vals.reshape(-1).astype(np.float64))

    w = rng.normal(0, 1, dim).astype(np.float32)
    r = rng.normal(0, 1, n).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(pair.dot(jnp.asarray(w))), x @ w, rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(pair.t_dot(jnp.asarray(r))), x.T @ r, rtol=2e-5, atol=2e-4)
    # squared (Hessian diagonal side)
    np.testing.assert_allclose(
        np.asarray(pair.squared().dot(jnp.asarray(w))), (x * x) @ w,
        rtol=2e-5, atol=2e-4)


def test_pair_autodiff(rng):
    """jax.grad through the pair must equal the transposed contraction."""
    import jax

    n, k, dim = 200, 5, 150
    cols = np.stack([rng.choice(dim, k, replace=False) for _ in range(n)])
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    pair = build_grr_pair(cols, vals, dim)
    r = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))

    def loss(w):
        return jnp.sum(pair.dot(w) * r)

    g = jax.grad(loss)(jnp.zeros(dim))
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(pair.t_dot(r)), rtol=2e-5, atol=2e-4)


# -- kernel (interpret mode) -------------------------------------------------

def test_kernel_interpret_matches_jnp(rng):
    from photon_ml_tpu.ops.grr_kernel import (
        grr_contract_jnp,
        grr_contract_kernel,
    )

    idx, seg, val = _coo(rng, 4000, 40000, 5000)
    d = build_grr_direction(idx, seg, val, 40000, 5000, cap=8,
                            dense_grid=False)
    table = jnp.asarray(rng.normal(0, 1, 40000).astype(np.float32))
    pad = d.n_gw * 16384 - d.table_len
    t = jnp.concatenate([table, jnp.zeros(pad, jnp.float32)])
    table_t = t.reshape(d.n_gw, 128, 128)
    out_j = grr_contract_jnp(table_t, d.g1, d.g2, d.g3, d.vals,
                             d.gw_of_st, d.ow_of_st, n_ow=d.n_ow, cap=d.cap)
    out_k = grr_contract_kernel(table_t, d.g1, d.g2, d.g3, d.vals,
                                d.gw_of_st, d.ow_of_st, d.first_of_ow,
                                n_ow=d.n_ow, cap=d.cap, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                               rtol=1e-5, atol=1e-5)


def test_dense_kernel_interpret_matches_jnp(rng):
    from photon_ml_tpu.ops.grr_kernel import (
        grr_contract_jnp_dense,
        grr_contract_kernel_dense,
    )

    idx, seg, val = _coo(rng, 40000, 40000, 5000)
    d = build_grr_direction(idx, seg, val, 40000, 5000, cap=8,
                            dense_grid=True)
    assert d.dense_grid
    table = jnp.asarray(rng.normal(0, 1, 40000).astype(np.float32))
    pad = d.n_gw * 16384 - d.table_len
    t = jnp.concatenate([table, jnp.zeros(pad, jnp.float32)])
    table_t = t.reshape(d.n_gw, 128, 128)
    out_j = grr_contract_jnp_dense(table_t, d.g1, d.g2, d.g3, d.vals,
                                   n_ow_p=d.n_ow_padded, cap=d.cap)
    out_k = grr_contract_kernel_dense(table_t, d.g1, d.g2, d.g3, d.vals,
                                      d.gw_of_st, n_ow_p=d.n_ow_padded,
                                      cap=d.cap, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                               rtol=1e-5, atol=1e-5)


def test_dense_grid_matches_legacy_layout(rng):
    """Same COO compiled both ways contracts identically."""
    idx, seg, val = _coo(rng, 30000, 70000, 70000)
    table = rng.normal(0, 1, 70000).astype(np.float32)
    want = _direct(idx, seg, val, table, 70000)
    for force in (True, False):
        d = build_grr_direction(idx, seg, val, 70000, 70000,
                                dense_grid=force)
        assert d.dense_grid == force
        np.testing.assert_allclose(
            np.asarray(d.contract(jnp.asarray(table))), want,
            rtol=2e-5, atol=2e-4)


# -- crossbar router (advisor findings) --------------------------------------

@pytest.mark.parametrize("native", [True, False])
def test_route_tile_random_permutations(rng, native, monkeypatch):
    if not native:
        monkeypatch.setenv("PHOTON_ML_TPU_NATIVE", "0")
        import photon_ml_tpu.native as nat
        monkeypatch.setattr(nat, "_lib", False)
    perm = rng.permutation(128 * 128).reshape(128, 128)
    g1, g2, g3 = route_tile(perm)
    x = rng.normal(0, 1, (128, 128)).astype(np.float32)
    out = apply_route_numpy(x, g1, g2, g3)
    want = np.empty_like(x)
    want.reshape(-1)[perm.reshape(-1)] = x.reshape(-1)
    np.testing.assert_array_equal(out, want)


def test_route_tile_identity_and_transpose(rng):
    iota = np.arange(128 * 128).reshape(128, 128)
    for perm in (iota, iota.T):
        g1, g2, g3 = route_tile(perm)
        x = rng.normal(0, 1, (128, 128)).astype(np.float32)
        out = apply_route_numpy(x, g1, g2, g3)
        want = np.empty_like(x)
        want.reshape(-1)[perm.reshape(-1)] = x.reshape(-1)
        np.testing.assert_array_equal(out, want)


def test_edge_color_native_rejects_bad_vertices(rng):
    """Out-of-range vertex ids must error, not corrupt memory."""
    from photon_ml_tpu.native import edge_color_native, native_available

    if not native_available():
        pytest.skip("native library unavailable")
    src = np.array([0, 1, 200, 3] * 32, np.int32)   # 200 >= n_left
    dst = np.array([0, 1, 2, 3] * 32, np.int32)
    with pytest.raises(ValueError):
        edge_color_native(src, dst, 128, 128, 128)


# -- objective integration ---------------------------------------------------

def test_objective_grr_matches_ell(rng):
    """Full GLM objective (value, grad, HVP, Hdiag) must agree between
    the GRR batch and the plain-ELL batch."""
    import jax

    from photon_ml_tpu.data.batch import make_sparse_batch
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.utils.synthetic import make_a1a_like

    rows, labels, _ = make_a1a_like(n=600, seed=3)
    dim = 123
    b_ell = make_sparse_batch(rows, dim, labels)
    b_grr = make_sparse_batch(rows, dim, labels, grr=True)
    assert b_grr.grr is not None
    obj = GLMObjective(
        loss=losses.LOGISTIC, reg=RegularizationContext.l2(0.5),
        norm=NormalizationContext.identity(),
    )
    w = jnp.asarray(rng.normal(0, 0.2, dim).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, dim).astype(np.float32))

    v1, g1_ = obj.value_and_gradient(w, b_ell)
    v2, g2_ = obj.value_and_gradient(w, b_grr)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1_), np.asarray(g2_),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(obj.hessian_vector(w, v, b_ell)),
        np.asarray(obj.hessian_vector(w, v, b_grr)), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(obj.hessian_diagonal(w, b_ell)),
        np.asarray(obj.hessian_diagonal(w, b_grr)), rtol=2e-4, atol=2e-4)
    # autodiff through the batch (the naive baseline path)
    ga = jax.grad(lambda w: obj.value(w, b_grr))(w)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(g1_),
                               rtol=2e-4, atol=2e-4)


def _assert_leaves_equal(a, b):
    """Two plans (directions, pairs, lists of pairs) hold the same
    bytes: one tree structure, and every leaf one dtype, shape and
    content."""
    import jax

    leaves_a, structure_a = jax.tree_util.tree_flatten(a)
    leaves_b, structure_b = jax.tree_util.tree_flatten(b)
    assert structure_a == structure_b and leaves_a
    for x, y in zip(leaves_a, leaves_b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.fixture
def builders(monkeypatch):
    """``builders.numpy()`` takes both C++ plan entries away, so the
    numpy body builds every plan, ``builders.numpy(ell=False)`` the COO
    entry alone (the route colouring stays native: the Python
    colourer's routes are proper but not the same bytes);
    ``builders.coo`` and ``builders.routed`` count the calls that
    reached the C++ COO entry and the router."""
    import types

    import photon_ml_tpu.native as nat

    if not nat.native_available():
        pytest.skip("native library unavailable")
    real_coo, real_routes = nat.grr_plan_native_coo, nat.grr_routes_native
    calls = types.SimpleNamespace(coo=[], routed=[])

    def coo(idx, *args, **kwargs):
        calls.coo.append(len(idx))
        return real_coo(idx, *args, **kwargs)

    def routes(dst, hi):
        calls.routed.append(dst.shape[0])
        return real_routes(dst, hi)

    def numpy(ell=True):
        monkeypatch.setattr(nat, "grr_plan_native_coo",
                            lambda *args, **kwargs: None)
        if ell:
            monkeypatch.setattr(nat, "grr_plan_native",
                                lambda *args, **kwargs: None)

    monkeypatch.setattr(nat, "grr_plan_native_coo", coo)
    monkeypatch.setattr(nat, "grr_routes_native", routes)
    calls.numpy = numpy
    return calls


def test_native_plan_matches_python_plan(rng, builders):
    """The C++ plan builder (pml_grr_plan, pml_grr_plan_coo) and the
    numpy body rank alike (scan order: numpy's argsort is stable), so
    at one cap they compile one input to the same bytes, leaf for leaf.
    They differ only in how an absent cap is resolved (the exact mean
    occupancy against a sample of segments), so the cap is pinned here.
    And the plan matches the dense reference."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.grr import build_grr_pair

    n, d, k = 700, 17000, 6
    block = d // k
    cols = np.minimum(
        (np.arange(k)[None, :] * block) + rng.integers(0, block, (n, k)),
        d - 1).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[rng.random((n, k)) < 0.15] = 0.0   # real zero entries drop

    pair_native = build_grr_pair(cols, vals, d, cap=8)
    builders.numpy()
    pair_python = build_grr_pair(cols, vals, d, cap=8)
    _assert_leaves_equal(pair_native, pair_python)

    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    r = jnp.asarray(rng.normal(size=n), jnp.float32)
    x = np.zeros((n, d), np.float32)
    np.add.at(x, (np.repeat(np.arange(n), k), cols.reshape(-1)),
              vals.reshape(-1))
    np.testing.assert_allclose(np.asarray(pair_native.dot(w)),
                               x @ np.asarray(w), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(pair_native.t_dot(r)),
                               x.T @ np.asarray(r), rtol=2e-3, atol=2e-3)


def _zipf_coo(rng, nnz, L, S, power=3.0):
    """Heavy repeat groups: segments drawn with a cubic skew."""
    seg = (S * rng.random(nnz) ** power).astype(np.int64)
    return rng.integers(0, L, nnz), seg, \
        rng.normal(0, 1, nnz).astype(np.float32)


def _scattered_spill_coo(rng):
    """Few entries over many blocks, as the column spill of the public
    KDD width: groups of six in 2,000 (segment, window) pairs spread
    over 40 table windows and five segment windows, so cap 4 spills
    two of each: 4,000 entries over some 200 blocks."""
    L, S = 40 * 16384, 20000
    seg = np.repeat(rng.choice(S, 2000, replace=False), 6)
    idx = (np.repeat(rng.integers(0, 40, 2000), 6) * 16384
           + rng.integers(0, 16384, 12000))
    return idx, seg, np.ones(12000, np.float32), L, S


COO_CASES = {
    # name: (entries, table_len, n_segments, build_grr_direction options)
    "sorted_keys_cap4": lambda rng: (
        tuple(a[np.argsort(_coo(rng, 30000, 70000, 9000)[1],
                           kind="stable")]
              for a in _coo(np.random.default_rng(0), 30000, 70000, 9000)),
        70000, 9000, dict(cap=4)),
    "unsorted_keys_cap8": lambda rng: (
        _coo(rng, 30000, 70000, 70000), 70000, 70000, dict(cap=8)),
    "cap64_one_window": lambda rng: (
        _zipf_coo(rng, 40000, 300, 150), 300, 150, dict(cap=64)),
    "zeros_among_val": lambda rng: (
        (lambda idx, seg, val: (idx, seg, np.where(
            rng.random(val.size) < 0.2, np.float32(0), val)))(
                *_coo(rng, 20000, 40000, 5000)),
        40000, 5000, dict(cap=4)),
    "sampled_cap_few_segments": lambda rng: (
        _zipf_coo(rng, 60000, 20000, 8000), 20000, 8000, dict()),
    "sampled_cap_many_segments": lambda rng: (
        _zipf_coo(rng, 60000, 20000, 30000), 20000, 30000, dict()),
    "empty": lambda rng: (
        (np.zeros(0, np.int64), np.zeros(0, np.int64),
         np.zeros(0, np.float32)), 17000, 17, dict()),
    "chain_three_levels": lambda rng: (
        _zipf_coo(rng, 120000, 3000, 3000), 3000, 3000,
        dict(cap=4, overflow_threshold=500)),
    "host_leaves": lambda rng: (
        _zipf_coo(rng, 50000, 3000, 3000), 3000, 3000,
        dict(cap=4, overflow_threshold=500, device=False)),
    "dense_grid_forced_on": lambda rng: (
        _coo(rng, 64, 17000 * 3, 17000), 17000 * 3, 17000,
        dict(cap=4, dense_grid=True)),
    "dense_grid_forced_off": lambda rng: (
        _coo(rng, 30000, 70000, 70000), 70000, 70000,
        dict(cap=8, dense_grid=False)),
    "level_fails_the_economy_test": lambda rng: (
        _scattered_spill_coo(rng)[:3], 40 * 16384, 20000,
        dict(cap=4, overflow_threshold=500)),
}


@pytest.mark.parametrize("case", sorted(COO_CASES))
def test_coo_direction_native_is_the_numpy_bodys_bytes(rng, case, builders):
    """``build_grr_direction`` through ``pml_grr_plan_coo`` against the
    numpy body behind it: every leaf of every level equal (ISSUE 33)."""
    import jax

    (idx, seg, val), L, S, options = COO_CASES[case](rng)
    native = build_grr_direction(idx, seg, val, L, S, **options)
    assert builders.coo and builders.coo[0] == np.count_nonzero(val)
    levels, d = 0, native
    while d is not None:
        levels, d = levels + 1, d.overflow
    if case == "chain_three_levels":
        assert levels >= 3
    # every level went to the C++, and was routed unless thrown away
    kept_levels = len(builders.routed)
    assert len(builders.coo) >= kept_levels == levels
    del builders.coo[:]
    builders.numpy()
    python = build_grr_direction(idx, seg, val, L, S, **options)
    assert not builders.coo
    _assert_leaves_equal(native, python)
    if options.get("device") is False:
        assert all(isinstance(leaf, np.ndarray)
                   for leaf in jax.tree_util.tree_leaves(native))
    if "dense_grid" in options:
        assert native.dense_grid is options["dense_grid"]
    if val.size:
        table = rng.normal(0, 1, L).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(native.contract(jnp.asarray(table))),
            _direct(idx, seg, val, table, S), rtol=2e-5, atol=5e-4)


@pytest.mark.parametrize("native", [True, False])
def test_level_failing_the_economy_test_is_never_filled(rng, native,
                                                       builders):
    """A level that streams more than the economy bound a entry is
    refused as soon as its supertiles are counted: the spill comes back
    as it went in, and nothing of the level is routed."""
    from photon_ml_tpu.data import grr

    idx, seg, val, L, S = _scattered_spill_coo(rng)
    if not native:
        builders.numpy()
    d = build_grr_direction(idx, seg, val, L, S, cap=4,
                            overflow_threshold=500, device=False)
    assert d.overflow is None and len(builders.routed) == 1
    assert len(builders.coo) == (2 if native else 0)
    m = int(np.count_nonzero(d.spill_val))
    assert m == 4000 and d.n_spill == 4000
    # the level as the parent built it before it asked
    whole = build_grr_direction(d.spill_idx, d.spill_seg, d.spill_val, L, S,
                                device=False)
    assert whole.n_supertiles * grr.SLOTS \
        > grr.ECONOMY_SLOTS_PER_ENTRY * m
    plain = build_grr_direction(idx, seg, val, L, S, cap=4, device=False)
    _assert_leaves_equal(d, plain)


def _blocks_coo(rng, nnz, n_gw, n_ow, blocks):
    """``nnz`` entries of value one over the given (ow, gw) blocks of an
    ``n_ow`` x ``n_gw`` grid at cap 4 (4,096 segments a window)."""
    ow, gw = np.asarray(blocks)[rng.integers(0, len(blocks), nnz)].T
    return (gw * 16384 + rng.integers(0, 16384, nnz),
            ow * 4096 + rng.integers(0, 4096, nnz),
            np.ones(nnz, np.float32), n_gw * 16384, n_ow * 4096)


# name: (entries, table windows, segment windows, blocks with entries);
# supertiles as laid out, slots an entry
ECONOMY_GRIDS = {
    # 9 of a 3 x 4 grid: dense, 12 supertiles, 65.5
    "full_grid": (3000, 3, 3, [(o, g) for o in range(3) for g in range(3)]),
    # one table window: dense, 8 supertiles, 4.4 (the floor's count)
    "one_window": (30000, 1, 8, [(o, 0) for o in range(8)]),
    # two blocks a segment window of 8 x 20: legacy order, 36, 147.5
    "diagonal": (4000, 8, 18, [(o, g % 8) for o in range(18)
                               for g in (o, o + 1)]),
    # 22 of 2 x 16 (under 0.7 of the grid): legacy order, 22, 180.2
    "under_dense_fill": (2000, 2, 16, [(o, 0) for o in range(16)]
                         + [(o, 1) for o in range(6)]),
    # 23 of 2 x 16: laid out as the whole grid, 32, 262.1
    "over_dense_fill": (2000, 2, 16, [(o, 0) for o in range(16)]
                        + [(o, 1) for o in range(7)]),
}


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("bound", [1, 3, 6, 48, 96, 160, 200, 400])
def test_economy_decision_is_the_whole_builds(rng, bound, native, builders,
                                              monkeypatch):
    """The early test keeps and refuses the levels the test on the
    finished level kept and refused (``n_supertiles`` as laid out,
    dense grid and all), on both sides of the bound and on both sides
    of the dense-grid rule."""
    from photon_ml_tpu.data import grr

    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", bound)
    if not native:
        builders.numpy()
    kept = {}
    for name, (nnz, n_gw, n_ow, blocks) in ECONOMY_GRIDS.items():
        idx, seg, val, L, S = _blocks_coo(rng, nnz, n_gw, n_ow, blocks)
        whole = build_grr_direction(idx, seg, val, L, S, device=False)
        assert whole.cap == 4 and (whole.n_gw, whole.n_ow) == (n_gw, n_ow)
        assert whole.dense_grid == (name not in ("diagonal",
                                                 "under_dense_fill"))
        want = whole.n_supertiles * grr.SLOTS <= bound * nnz
        del builders.routed[:]
        level, s_idx, _s_seg, _s_val = grr._spill_overflow(
            idx.astype(np.int32), seg.astype(np.int32), val, nnz, L, S,
            True, 0, device=False, depth=1)
        assert (level is not None) == want
        if level is None:
            assert s_idx.size == nnz and not builders.routed
        else:
            _assert_leaves_equal(level, whole)
        kept[name] = want
    assert sorted(name for name in kept if kept[name]) == {
        1: [], 3: [], 6: ["one_window"], 48: ["one_window"],
        96: ["full_grid", "one_window"],
        160: ["diagonal", "full_grid", "one_window"],
        200: ["diagonal", "full_grid", "one_window", "under_dense_fill"],
        400: sorted(ECONOMY_GRIDS)}[bound]


BAD_IDS = {
    "idx_high": ([0, 1, 50], [0, 1, 2]),
    "idx_negative": ([0, -1, 2], [0, 1, 2]),
    "seg_high": ([0, 1, 2], [0, 7, 1]),
    "seg_negative": ([0, 1, 2], [-1, 0, 1]),
}


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("bad", sorted(BAD_IDS))
def test_coo_id_out_of_range_raises(bad, native, builders):
    """One error from ``build_grr_direction`` whichever builder is
    behind it; the C++ entry, called alone, makes the same check."""
    import photon_ml_tpu.native as nat

    idx, seg = (np.array(ids) for ids in BAD_IDS[bad])
    one = np.ones(3, np.float32)
    if native:
        with pytest.raises(ValueError, match="idx or seg out of range"):
            nat.grr_plan_native_coo(idx, seg, one, 50, 7, 4)
    else:
        builders.numpy()
    with pytest.raises(ValueError, match=f"^{bad[:3]} out of range$"):
        build_grr_direction(idx, seg, one, 50, 7, cap=4)
    # under a zero value it is no entry
    zero = np.where((idx < 0) | (idx >= 50) | (seg < 0) | (seg >= 7),
                    np.float32(0), one)
    assert build_grr_direction(idx, seg, zero, 50, 7,
                               cap=4).n_supertiles == 1


@pytest.mark.parametrize("which", ["idx", "seg"])
def test_coo_id_beyond_int32_raises_and_does_not_wrap(which, builders):
    """2**32 + 5 narrowed to int32 is 5, in range: a plan built from
    it would be silently wrong."""
    import photon_ml_tpu.native as nat

    ok, one = np.array([0, 1, 2]), np.ones(3, np.float32)
    wide = np.array([0, 1, 2**32 + 5], np.int64)
    idx, seg = (wide, ok) if which == "idx" else (ok, wide)
    with pytest.raises(ValueError, match=f"^{which} out of range$"):
        build_grr_direction(idx, seg, one, 50, 7, cap=4)
    with pytest.raises(ValueError, match=f"{which} id exceeds int32"):
        nat.grr_plan_native_coo(idx, seg, one, 2**40, 2**40, 4)


def test_bad_cap_rejected_both_paths(rng):
    from photon_ml_tpu.data.grr import build_grr_pair

    cols = rng.integers(0, 50, (20, 3)).astype(np.int32)
    vals = rng.normal(size=(20, 3)).astype(np.float32)
    with pytest.raises(ValueError, match="cap"):
        build_grr_pair(cols, vals, 50, cap=48)


def test_overflow_level_absorbs_spill(rng):
    """Two-level plan: heavy-tail spill recompiled at a larger cap; the
    overflow contraction must reproduce the single-level result and the
    dense reference."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.grr import build_grr_pair

    n, d, k = 600, 300, 6
    # Skewed columns: a few columns soak up most entries (below the
    # dense-hot threshold, above per-window cap) -> guaranteed spill.
    cols = np.where(
        rng.random((n, k)) < 0.5,
        rng.integers(0, 8, (n, k)),
        rng.integers(0, d, (n, k)),
    ).astype(np.int32)
    cols = np.sort(cols, axis=1)
    for j in range(1, k):
        bump = cols[:, j] <= cols[:, j - 1]
        cols[bump, j] = cols[bump, j - 1] + 1
    cols = np.minimum(cols, d - 1)
    vals = rng.normal(size=(n, k)).astype(np.float32)

    plain = build_grr_pair(cols, vals, d, hot_threshold=10**9,
                           overflow_threshold=10**9)
    two_level = build_grr_pair(cols, vals, d, hot_threshold=10**9,
                               overflow_threshold=1)
    assert (two_level.col_dir.overflow is not None
            or two_level.row_dir.overflow is not None), \
        "expected at least one direction to carry an overflow plan"

    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    r = jnp.asarray(rng.normal(size=n), jnp.float32)
    np.testing.assert_allclose(np.asarray(two_level.dot(w)),
                               np.asarray(plain.dot(w)),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(two_level.t_dot(r)),
                               np.asarray(plain.t_dot(r)),
                               rtol=2e-4, atol=2e-4)
    # Hessian-diagonal path recurses into the overflow too.
    np.testing.assert_allclose(
        np.asarray(two_level.squared().t_dot(jnp.abs(r))),
        np.asarray(plain.squared().t_dot(jnp.abs(r))),
        rtol=2e-4, atol=2e-4)


def test_mid_hot_columns_split(rng):
    """Power-law columns: mega-hot → dense side, mid-hot → compact
    col_mid plan, tail → main plan; contraction exact throughout."""
    n, k, dim = 4096, 8, 2000
    # ~6 mega-hot columns (0..5 in most rows), a band of mid-hot
    # columns (6..29 frequently), and a uniform tail.
    cols = np.zeros((n, k), np.int64)
    cols[:, 0] = rng.integers(0, 6, n)                  # mega-hot
    cols[:, 1] = rng.integers(6, 30, n)                 # mid-hot band
    cols[:, 2:] = rng.integers(30, dim, (n, k - 2))
    # de-duplicate per row (resample collisions into distinct slots)
    for j in range(1, k):
        for _ in range(6):
            dup = (cols[:, j:j + 1] == cols[:, :j]).any(axis=1)
            if not dup.any():
                break
            lo = 6 if j == 1 else 30
            cols[dup, j] = rng.integers(lo, dim, int(dup.sum()))
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    pair = build_grr_pair(cols.astype(np.int32), vals, dim,
                          hot_threshold=500, mid_threshold=40)
    assert pair.hot_ids.shape[0] > 0          # mega-hot split happened
    assert pair.col_mid is not None           # mid plan exists
    assert pair.mid_ids.shape[0] > 0

    x = np.zeros((n, dim), np.float64)
    np.add.at(x, (np.repeat(np.arange(n), k), cols.reshape(-1)),
              vals.reshape(-1).astype(np.float64))
    w = rng.normal(0, 1, dim).astype(np.float32)
    r = rng.normal(0, 1, n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pair.dot(jnp.asarray(w))),
                               x @ w, rtol=2e-5, atol=3e-4)
    np.testing.assert_allclose(np.asarray(pair.t_dot(jnp.asarray(r))),
                               x.T @ r, rtol=2e-5, atol=3e-4)
    np.testing.assert_allclose(
        np.asarray(pair.squared().t_dot(jnp.asarray(r))),
        (x * x).T @ r, rtol=2e-5, atol=3e-4)


def test_max_hot_bytes_budget(rng):
    """The dense hot side respects its HBM byte budget."""
    n, k, dim = 2048, 4, 64
    cols = np.stack([rng.choice(dim, k, replace=False)
                     for _ in range(n)]).astype(np.int32)
    vals = np.ones((n, k), np.float32)
    # Without a budget nearly every column densifies (small-d regime);
    # with a tight budget H collapses to the allowance.
    free = build_grr_pair(cols, vals, dim)
    tight = build_grr_pair(cols, vals, dim, max_hot_bytes=4 * n * 3)
    assert free.hot_ids.shape[0] > 3
    assert tight.hot_ids.shape[0] <= 3
    w = rng.normal(0, 1, dim).astype(np.float32)
    np.testing.assert_allclose(np.asarray(tight.dot(jnp.asarray(w))),
                               np.asarray(free.dot(jnp.asarray(w))),
                               rtol=2e-5, atol=3e-4)


def test_overflow_chain_recurses(rng):
    """Power-law tails absorb through MULTIPLE overflow levels: the
    chain leaves less COO residual than a single level, and the
    contraction stays exact."""
    nnz, L, S = 120_000, 3000, 3000
    # Zipf-ish segments: heavy repeat groups spanning several levels.
    seg = (S * rng.random(nnz) ** 3.0).astype(np.int64)
    idx = rng.integers(0, L, nnz)
    val = rng.normal(0, 1, nnz).astype(np.float32)
    chain = build_grr_direction(idx, seg, val, L, S, cap=4,
                                overflow_threshold=500)
    shallow = build_grr_direction(idx, seg, val, L, S, cap=4,
                                  overflow_threshold=500,
                                  overflow_depth=1)

    def walk(d):
        depth, residual = 0, 0
        while d is not None:
            residual = int(np.count_nonzero(np.asarray(d.spill_val)))
            depth += 1
            d = d.overflow
        return depth, residual

    depth, residual = walk(chain)
    depth1, residual1 = walk(shallow)
    assert depth >= 3          # lvl1 + at least two overflow levels
    assert depth1 == 2
    assert residual < residual1   # deeper chain absorbs more
    table = rng.normal(0, 1, L).astype(np.float32)
    for d in (chain, shallow):
        np.testing.assert_allclose(
            np.asarray(d.contract(jnp.asarray(table))),
            _direct(idx, seg, val, table, S), rtol=2e-5, atol=5e-4)


def test_overflow_chain_depth_capped(rng):
    """A single mega-segment (each level absorbs only ~cap·n_gw
    entries) must terminate at the depth cap, not recurse unboundedly
    (review-confirmed RecursionError without the cap)."""
    nnz, L, S = 300_000, 100_000, 3000
    idx = rng.integers(0, L, nnz)
    seg = np.zeros(nnz, np.int64)
    val = rng.normal(0, 1, nnz).astype(np.float32)
    d = build_grr_direction(idx, seg, val, L, S, cap=4,
                            overflow_threshold=500)
    depth = 0
    while d is not None:
        depth += 1
        d = d.overflow
    assert depth <= 5          # lvl1 + at most overflow_depth=4 levels


def _powerlaw_ell(rng, n, k, dim, x0=3000.0):
    """Reciprocal (CTR-shaped) column popularity: P(col) ∝ 1/(col+x0),
    concentrating ~half the mass in table window 0 while spreading it
    across the window (the KDD shape PERF.md's range-split lever
    targets)."""
    u = rng.uniform(size=(n, k))
    cols = np.minimum(x0 * np.exp(u * np.log((dim + x0) / x0)) - x0,
                      dim - 1).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    return cols, vals


def test_plan_col_ranges_uniform_none(rng):
    from photon_ml_tpu.data.grr import _plan_col_ranges

    n, k, dim = 5000, 8, 70000
    cols = rng.integers(0, dim, (n, k)).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    assert _plan_col_ranges(cols, vals, dim) is None
    # single-window dims can never split
    assert _plan_col_ranges(cols % 9000, vals, 9000) is None
    # denser uniform data with an UNALIGNED dim must not split either:
    # the partial trailing window's occupancy is lower only because the
    # window is narrower (review finding — this exact shape used to
    # return a spurious 2-part split)
    n, k = 12000, 20
    cols = rng.integers(0, dim, (n, k)).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    assert _plan_col_ranges(cols, vals, dim) is None


def test_plan_col_ranges_powerlaw(rng):
    from photon_ml_tpu.data.grr import WIN, _plan_col_ranges

    n, k, dim = 12000, 20, 70000
    cols, vals = _powerlaw_ell(rng, n, k, dim)
    ranges = _plan_col_ranges(cols, vals, dim)
    assert ranges is not None and len(ranges) >= 2
    # window-aligned contiguous partition of [0, dim)
    assert ranges[0][0] == 0 and ranges[-1][1] == dim
    for (lo, hi, frac), (lo2, _, _) in zip(ranges, ranges[1:]):
        assert hi == lo2 and lo % WIN == 0
    assert abs(sum(f for _, _, f in ranges) - 1.0) < 1e-9


def test_col_range_split_matches_global(rng):
    """The split row plan must reproduce the global plan's contraction
    exactly (same products, reordered sums) and the direct reference."""
    import jax.numpy as jnp

    from photon_ml_tpu.data.grr import GrrRangeSplit

    n, k, dim = 12000, 20, 70000
    cols, vals = _powerlaw_ell(rng, n, k, dim)
    pg = build_grr_pair(cols, vals, dim, col_range_split=False)
    ps = build_grr_pair(cols, vals, dim, col_range_split=True)
    assert isinstance(ps.row_dir, GrrRangeSplit)
    assert not isinstance(pg.row_dir, GrrRangeSplit)

    w = rng.normal(0, 1, dim).astype(np.float32)
    a = np.asarray(pg.dot(jnp.asarray(w)))
    b = np.asarray(ps.dot(jnp.asarray(w)))
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    direct = np.zeros(n, np.float64)
    np.add.at(direct, np.repeat(np.arange(n), k),
              (vals.astype(np.float64) * w[cols]).reshape(-1))
    np.testing.assert_allclose(b, direct, rtol=2e-3, atol=2e-3)
    r = rng.normal(0, 1, n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pg.t_dot(jnp.asarray(r))),
                               np.asarray(ps.t_dot(jnp.asarray(r))),
                               rtol=2e-4, atol=2e-4)
    # squared() (hessian-diagonal path) survives the split
    np.testing.assert_allclose(
        np.asarray(pg.squared().dot(jnp.asarray(w))),
        np.asarray(ps.squared().dot(jnp.asarray(w))),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", ["uniform_mid", "powerlaw_split"])
def test_pair_leaves_equal_routed_in_blocks_or_in_one_call(
        rng, shape, monkeypatch):
    """Routing the supertiles in blocks on every core (ISSUE 29) moves
    no byte of a plan: every leaf of a pair whose routed calls cross the
    block threshold equals the leaf built with every call held to one
    block, which runs inline as one serial ``pml_grr_routes``."""
    import jax

    import photon_ml_tpu.native as nat

    if not nat.native_available():
        pytest.skip("native library unavailable")
    if shape == "uniform_mid":
        n, k, dim = 40000, 8, 5000
        cols = rng.integers(0, dim, size=(n, k)).astype(np.int32)
        vals = rng.normal(size=(n, k)).astype(np.float32)
        split = None
    else:
        n, k, dim = 12000, 20, 70000
        cols, vals = _powerlaw_ell(rng, n, k, dim)
        split = True
    real, routed = nat.grr_routes_native, []

    def recording(dst, hi):
        routed.append(dst.shape[0])
        return real(dst, hi)

    monkeypatch.setattr(nat, "grr_routes_native", recording)
    blocked = build_grr_pair(cols, vals, dim, col_range_split=split)
    assert max(routed) > 2 * nat._ROUTE_BLOCK, routed
    calls = sorted(routed)
    del routed[:]
    monkeypatch.setattr(nat, "_ROUTE_BLOCK", 1 << 40)
    whole = build_grr_pair(cols, vals, dim, col_range_split=split)
    assert sorted(routed) == calls
    leaves, structure = jax.tree_util.tree_flatten(blocked)
    leaves_whole, structure_whole = jax.tree_util.tree_flatten(whole)
    assert structure == structure_whole and leaves
    for a, b in zip(leaves, leaves_whole):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _levels(direction):
    out = []
    while direction is not None:
        out.append(direction)
        direction = direction.overflow
    return out


@pytest.mark.parametrize("build", ["resident", "sharded_x8"])
def test_pair_leaves_equal_native_coo_levels_or_numpy_levels(
        rng, build, builders):
    """A whole pair with a mid split and overflow levels in both
    directions: every overflow level and the mid plan through
    ``pml_grr_plan_coo`` (ISSUE 33), against the numpy body building
    them as it did before.  The first levels come from the ELL arrays
    through ``pml_grr_plan`` on both sides, so an absent cap resolves
    alike.  Eight shards: the mesh build's pooled overflow and forced
    mid set."""
    from photon_ml_tpu.data.grr import build_sharded_grr_pairs

    n, k, dim = 20000, 12, 40000
    cols, vals = _powerlaw_ell(rng, n, k, dim, x0=300.0)

    def make():
        if build == "resident":
            return [build_grr_pair(cols, vals, dim, overflow_threshold=500)]
        per = n // 8
        return build_sharded_grr_pairs(
            [cols[i * per:(i + 1) * per] for i in range(8)],
            [vals[i * per:(i + 1) * per] for i in range(8)], dim,
            overflow_threshold=500, mid_threshold=60)

    native = make()
    for pair in native:
        row = pair.row_dir
        assert len(_levels(pair.col_dir)) >= 2
        assert len(_levels(pair.col_mid)) >= 2
        assert max(len(_levels(p)) for p in getattr(row, "parts", (row,))) \
            >= (5 if build == "resident" else 2)
    # every level past the first and every mid plan went to the C++
    n_coo = sum(len(_levels(d)[1:]) + (d is pair.col_mid)
                for pair in native
                for d in (pair.col_dir, pair.col_mid)
                + tuple(getattr(pair.row_dir, "parts", (pair.row_dir,))))
    assert len(builders.coo) >= n_coo
    del builders.coo[:]
    builders.numpy(ell=False)
    python = make()
    assert not builders.coo
    _assert_leaves_equal(native, python)


@pytest.mark.parametrize("without", ["the_two_entries", "the_library"])
def test_pair_leaves_equal_native_hot_split_or_numpy_body(
        rng, monkeypatch, hot_split, spans_of, without):
    """A whole pair with all three column classes and a mid split: the
    count and the class split through the native library (ISSUE 40: the
    cold build's count, the mid split's count, ``_split_classes``)
    against ``np.bincount`` and the numpy body, with the other native
    builders in place and with ``PHOTON_ML_TPU_NATIVE=0``: every leaf
    equal, one sha256 over the pair, and the stages say who ran."""
    import photon_ml_tpu.native as native_lib
    from photon_ml_tpu.data import grr

    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 2)
    n, k, dim = 17000, 8, 30000
    cols, vals = _powerlaw_ell(rng, n, k, dim, x0=300.0)
    cols[:, 0] = 0
    vals[rng.random((n, k)) < 0.05] = 0.0

    def build():
        pair, spans = spans_of(build_grr_pair, cols, vals, dim)
        said = {s["name"]: s["args"] for s in spans
                if s["name"] in ("grr_hot_split", "grr_mid_split")}
        return pair, said

    native, said = build()
    assert hot_split.calls == ["count", "split", "count"]
    assert native.hot_ids.size and native.planned_ids.size
    assert native.tail.nnz and native.mid_ids.size
    hot = said["grr_hot_split"]
    assert (hot["native"], hot["entries"]) == (1, n * k)
    assert hot["workers"] == native_lib._split_workers(n * k) == min(
        2, native_lib._usable_cores())
    assert said["grr_mid_split"]["native"] == 1
    del hot_split.calls[:]
    hot_split.without(without)
    numpy, said = build()
    assert not hot_split.calls
    hot = said["grr_hot_split"]
    assert (hot["native"], hot["workers"], hot["entries"]) == (0, 1, n * k)
    assert said["grr_mid_split"]["native"] == 0
    hot_split.same_bytes(native, numpy)


def test_pair_leaves_equal_at_one_fill_thread_or_many_or_numpy_body(
        rng, monkeypatch, hot_split, spans_of, sha256_of):
    """A whole pair with all three column classes, a mid split and kept
    overflow levels: through the native library with the
    plans' fills on one thread and on up to seven, and with
    ``PHOTON_ML_TPU_NATIVE=0``: one sha256 over the pair.  Every plan
    filled says so on its ``grr_plan_scan`` or ``grr_overflow_level``:
    ``workers`` min(cores, its output windows), 1 inline; a refused
    level 0."""
    import jax

    import photon_ml_tpu.native as native_lib
    from photon_ml_tpu.data import grr

    # a tail at this height, and overflow levels dense enough to keep
    monkeypatch.setattr(grr, "ECONOMY_SLOTS_PER_ENTRY", 7)
    n, k, dim = 17000, 5, 30000
    cols, vals = _powerlaw_ell(rng, n, k, dim, x0=300.0)
    cols[:, 0] = 0
    vals[rng.random((n, k)) < 0.05] = 0.0

    def build(cores):
        monkeypatch.setattr(native_lib, "_usable_cores", lambda: cores)
        pair, spans = spans_of(build_grr_pair, cols, vals, dim, cap=4,
                               overflow_threshold=2000)
        said = [s["args"] for s in spans
                if s["name"] in ("grr_plan_scan", "grr_overflow_level")]
        assert all(a["kept"] or a["workers"] == 0 for a in said
                   if "kept" in a)
        return pair, said

    digests = set()
    for cores in (1, 7):
        pair, said = build(cores)
        digests.add(sha256_of(
            [np.asarray(x) for x in jax.tree_util.tree_leaves(pair)]))
        assert all(a["native"] == 1 for a in said)
        plans = [d for top in (pair.col_dir, pair.col_mid)
                 + tuple(getattr(pair.row_dir, "parts", (pair.row_dir,)))
                 for d in _levels(top)]
        assert sorted(a["workers"] for a in said if a["workers"]) \
            == sorted(min(cores, d.n_ow) for d in plans)
    assert pair.tail.nnz and pair.mid_ids.size
    assert max(len(_levels(pair.col_dir)), len(_levels(pair.col_mid)),
               len(_levels(pair.row_dir))) >= 3
    assert {min(7, d.n_ow) for d in plans} >= {1, 5}     # inline, threads
    hot_split.without("the_library")
    numpy, said = build(7)
    assert all(a["native"] == 0 for a in said)
    assert {a["workers"] for a in said} <= {0, 1}
    hot_split.same_bytes(pair, numpy)
    assert digests == {sha256_of(
        [np.asarray(x) for x in jax.tree_util.tree_leaves(numpy)])}


def test_col_range_split_reduces_spill(rng):
    """On power-law columns the per-range capacities must hold in the
    level-1 kernel what the single global cap pushed to overflow/COO
    (round-4 verdict item #1's 'done' criterion)."""
    n, k, dim = 12000, 20, 70000
    cols, vals = _powerlaw_ell(rng, n, k, dim)
    sg = build_grr_pair(
        cols, vals, dim, col_range_split=False).row_dir.plan_stats()
    ss = build_grr_pair(
        cols, vals, dim, col_range_split=True).row_dir.plan_stats()
    assert ss["spill_frac"] < sg["spill_frac"] / 3
    assert ss["coo_frac"] < 0.01
    assert len(set(ss["cap"])) >= 2   # ranges actually chose own caps


def test_idx_range_native_matches_numpy(rng):
    """The C++ builder's in-stream range filter must agree with the
    numpy fallback's filtered-COO build."""
    import jax.numpy as jnp

    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data.grr import WIN, _build_direction_ell

    if not nat.native_available():
        pytest.skip("native library unavailable")
    n, k, dim = 3000, 10, 50000
    cols, vals = _powerlaw_ell(rng, n, k, dim, x0=2000.0)
    vals[rng.random((n, k)) < 0.1] = 0.0
    lo, hi = WIN, 3 * WIN
    d_native = _build_direction_ell(cols, vals, 0, dim, n, None, True,
                                    None, idx_range=(lo, hi))
    saved = nat._lib
    nat._lib = None
    try:
        d_numpy = _build_direction_ell(cols, vals, 0, dim, n, None, True,
                                       None, idx_range=(lo, hi))
    finally:
        nat._lib = saved
    assert d_native.table_len == hi - lo == d_numpy.table_len
    w = rng.normal(0, 1, dim).astype(np.float32)
    out_n = np.asarray(d_native.contract(jnp.asarray(w[lo:hi])))
    out_p = np.asarray(d_numpy.contract(jnp.asarray(w[lo:hi])))
    np.testing.assert_allclose(out_n, out_p, rtol=2e-4, atol=2e-4)
    keep = (cols >= lo) & (cols < hi)
    direct = np.zeros(n, np.float64)
    np.add.at(direct, np.repeat(np.arange(n), k),
              (np.where(keep, vals, 0).astype(np.float64)
               * w[np.minimum(cols, dim - 1)]).reshape(-1))
    np.testing.assert_allclose(out_n, direct, rtol=2e-3, atol=2e-3)


def test_spill_warning_rate_limited(caplog):
    """Satellite (round 8): inside a plan build the per-direction "GRR
    spill fraction" warning aggregates into ONE count/min/max/mean
    summary (a four-device dry run drowned the end of its log in ~20
    identical lines); outside any build scope (ISSUE 16 satellite) a flagged
    burst dedupes into a time-windowed summary instead of one raw line
    per call."""
    import logging

    from photon_ml_tpu.data.grr import _spill_warnings

    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.data.grr"):
        caplog.clear()
        _spill_warnings.note(1, 100)            # stale unscoped clean
        with _spill_warnings:                   # build: discarded on
            # scope entry — must NOT inflate this scope's denominator
            for _ in range(20):
                _spill_warnings.note(20, 100)   # 20% on the XLA path
            _spill_warnings.note(1, 100)        # under threshold
            assert not caplog.records           # silent while collecting
        assert len(caplog.records) == 1
        msg = caplog.records[0].getMessage()
        assert "20 of 21 direction builds" in msg
        assert ("min 20.0%" in msg and "max 20.0%" in msg
                and "mean 20.0%" in msg)

        caplog.clear()
        with _spill_warnings:                   # clean builds: no line
            _spill_warnings.note(0, 100)
        assert not caplog.records

        caplog.clear()
        _spill_warnings._last_emit = None       # fresh dedupe window
        _spill_warnings.note(20, 100)           # outside a build scope
        assert len(caplog.records) == 1         # first one is immediate
        assert "1 of 1 direction builds" in \
            caplog.records[0].getMessage()
        for _ in range(10):                     # burst inside the window
            _spill_warnings.note(30, 100)
        assert len(caplog.records) == 1         # ...buffers silently
        _spill_warnings._last_emit = -1e9       # window elapsed
        _spill_warnings.note(40, 100)
        assert len(caplog.records) == 2         # ONE summary for the burst
        msg = caplog.records[1].getMessage()
        assert "11 of 11 direction builds" in msg
        assert "min 30.0%" in msg and "max 40.0%" in msg


def test_spill_warning_unscoped_burst_flushed_by_scope(caplog):
    """An unscoped buffered burst is flushed (as its own summary) when
    a build scope opens, so the scope's summary counts only its own
    direction builds."""
    import logging

    from photon_ml_tpu.data.grr import _spill_warnings

    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.data.grr"):
        caplog.clear()
        _spill_warnings._last_emit = None
        _spill_warnings.note(20, 100)           # immediate (1 of 1)
        _spill_warnings.note(25, 100)           # buffered in the window
        assert len(caplog.records) == 1
        with _spill_warnings:
            assert len(caplog.records) == 2     # burst flushed at enter
            assert "1 of 1 direction builds" in \
                caplog.records[1].getMessage()
            _spill_warnings.note(30, 100)
        assert len(caplog.records) == 3
        assert "1 of 1 direction builds" in \
            caplog.records[2].getMessage()


def test_spill_warning_aggregates_across_sharded_builds(caplog):
    """Satellite (round 9): a multi-build operation — several plan
    builds inside one ``collect_spill_warnings`` scope, the shape of
    ``build_chunked_batch``/``shard_sparse_batch`` — emits ONE summary
    for the whole sharded build, not one line per sub-plan (a
    four-device dry run printed 15+)."""
    import logging

    from photon_ml_tpu.data.grr import (
        _spill_warnings,
        collect_spill_warnings,
    )

    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.data.grr"):
        caplog.clear()
        with collect_spill_warnings():
            for _ in range(3):            # three sibling plan builds
                with _spill_warnings:     # each with its own scope
                    for _ in range(5):    # five direction builds each
                        _spill_warnings.note(20, 100)
            assert not caplog.records     # silent until outermost exit
        assert len(caplog.records) == 1
        assert "15 of 15 direction builds" in \
            caplog.records[0].getMessage()


def test_chunked_grr_build_one_spill_summary(rng, caplog):
    """The real path: a GRR-layout chunked build (per-chunk sub-plans
    through build_sharded_grr_pairs) logs at most one spill summary."""
    import logging

    from photon_ml_tpu.data.chunked_batch import build_chunked_batch
    from photon_ml_tpu.data.sparse_rows import SparseRows

    n, d, k = 2048, 4000, 6
    x0 = d / 14.0
    u = rng.uniform(size=(n, k))
    cols = np.minimum(x0 * np.exp(u * np.log((d + x0) / x0)) - x0,
                      d - 1).astype(np.int64)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    rows = SparseRows.from_flat(np.arange(n + 1, dtype=np.int64) * k,
                                cols.reshape(-1), vals.reshape(-1))
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.data.grr"):
        caplog.clear()
        build_chunked_batch(rows, d, labels, n_chunks=4, layout="grr",
                            row_capacity=k)
        spill_lines = [r for r in caplog.records
                       if "spill fraction" in r.getMessage()]
        assert len(spill_lines) <= 1
