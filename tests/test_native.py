"""Tests for the native C++ ETL library (photon_ml_tpu.native).

The native and numpy paths must be byte-identical: the native library is
a drop-in accelerator, not a second implementation with its own
semantics.  If no toolchain is available these tests skip (the fallback
path is what every other test exercises).
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.native import (
    _ROUTE_BLOCK,
    _ptr,
    colmajor_build_native,
    grr_routes_native,
    lib,
    libsvm_parse_native,
)

pytestmark = pytest.mark.skipif(
    lib() is None, reason="native library unavailable (no toolchain?)"
)


def test_libsvm_native_matches_python(tmp_path, rng):
    from photon_ml_tpu.io.libsvm import read_libsvm

    path = str(tmp_path / "data.libsvm")
    lines = [
        "+1 1:0.5 3:1 7:-2.25  # trailing comment",
        "-1 2:1e-3 3:0.75",
        "# full-line comment",
        "",
        "-1 5:4 5:1 9:2",        # duplicate index -> summed
        "+1 12:1",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")

    os.environ["PHOTON_ML_TPU_NATIVE"] = "1"
    rows_n, y_n, dim_n = read_libsvm(path)

    # Python reference: call the body with native disabled.
    os.environ["PHOTON_ML_TPU_NATIVE"] = "0"
    try:
        import photon_ml_tpu.native as nat

        nat._lib = None  # force fallback despite cached lib
        rows_p, y_p, dim_p = read_libsvm(path)
    finally:
        os.environ.pop("PHOTON_ML_TPU_NATIVE", None)
        nat._lib = False  # restore lazy load

    assert dim_n == dim_p
    np.testing.assert_array_equal(y_n, y_p)
    assert len(rows_n) == len(rows_p)
    for (cn, vn), (cp, vp) in zip(rows_n, rows_p):
        np.testing.assert_array_equal(cn, cp)
        np.testing.assert_allclose(vn, vp, rtol=1e-6)


def test_libsvm_native_zero_based(tmp_path):
    from photon_ml_tpu.io.libsvm import read_libsvm

    path = str(tmp_path / "zb.libsvm")
    with open(path, "w") as f:
        f.write("1 0:2.0 4:1.0\n0 1:3.0\n")
    rows, y, dim = read_libsvm(path, zero_based=True,
                               binary_labels_to_01=False)
    assert dim == 5
    np.testing.assert_array_equal(rows[0][0], [0, 4])
    np.testing.assert_array_equal(y, [1.0, 0.0])


def test_libsvm_native_malformed_raises(tmp_path):
    path = str(tmp_path / "bad.libsvm")
    with open(path, "w") as f:
        f.write("1 3:abc\n")
    with open(path, "rb") as f:
        data = f.read()
    with pytest.raises(ValueError):
        libsvm_parse_native(data)


@pytest.mark.parametrize("capacity", [8, 16])
def test_colmajor_native_matches_numpy(rng, capacity):
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data.colmajor import build_colmajor

    n, k, dim = 64, 6, 40
    cols = rng.integers(0, dim, (n, k)).astype(np.int32)
    vals = rng.normal(0, 1, (n, k)).astype(np.float32)
    vals[rng.uniform(size=(n, k)) < 0.2] = 0.0    # ELL padding holes

    native = colmajor_build_native(cols, vals, dim, capacity)
    assert native is not None
    tvals_n, trows_n, vcol_n = native

    nat._lib = None  # numpy path
    try:
        cm = build_colmajor(cols, vals, dim, capacity=capacity)
    finally:
        nat._lib = False
    np.testing.assert_array_equal(tvals_n, np.asarray(cm.tvals))
    np.testing.assert_array_equal(trows_n, np.asarray(cm.trows))
    np.testing.assert_array_equal(vcol_n, np.asarray(cm.vcol))


def test_colmajor_native_pad_vrows_to(rng):
    cols = rng.integers(0, 10, (16, 3)).astype(np.int32)
    vals = np.ones((16, 3), np.float32)
    out = colmajor_build_native(cols, vals, 10, 8, pad_vrows_to=64)
    assert out is not None and out[0].shape == (64, 8)
    with pytest.raises(ValueError, match="pad_vrows_to"):
        colmajor_build_native(cols, vals, 10, 1, pad_vrows_to=2)


# -- GRR route colouring on every host core (ISSUE 29) ------------------------

def _route_tiles(rng, n_st):
    """``n_st`` random slot bijections and gather planes."""
    dst = np.empty((n_st, 128, 128), np.int32)
    for t in range(n_st):
        dst[t] = rng.permutation(128 * 128).reshape(128, 128)
    hi = rng.integers(0, 128, size=(n_st, 128, 128)).astype(np.int8)
    return dst, hi


def _one_serial_call(dst, hi):
    """``pml_grr_routes`` itself, once, over every tile."""
    out = [np.empty_like(hi) for _ in range(3)]
    rc = lib().pml_grr_routes(_ptr(dst), _ptr(hi), dst.shape[0],
                              *map(_ptr, out))
    assert rc == 0
    return out


ROUTE_SIZES = {"none": 0, "one": 1, "block_less_one": _ROUTE_BLOCK - 1,
               "block": _ROUTE_BLOCK, "block_and_one": _ROUTE_BLOCK + 1,
               "ragged_tail": 3 * _ROUTE_BLOCK + 5}


@pytest.mark.parametrize("size", sorted(ROUTE_SIZES))
def test_grr_routes_blocked_is_one_serial_call(rng, size):
    dst, hi = _route_tiles(rng, ROUTE_SIZES[size])
    routed = grr_routes_native(dst, hi)
    for got, want in zip(routed, _one_serial_call(dst, hi)):
        assert got.dtype == np.int8 and got.shape == hi.shape
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_grr_routes_bad_tile_in_any_block_raises(rng, where):
    n_st = ROUTE_SIZES["ragged_tail"]
    dst, hi = _route_tiles(rng, n_st)
    tile = {"first": 2, "middle": _ROUTE_BLOCK + 3, "last": n_st - 1}[where]
    dst[tile, 5, 7] = dst[tile, 0, 0]       # one slot twice: no bijection
    with pytest.raises(ValueError, match="not a bijection"):
        grr_routes_native(dst, hi)


def test_grr_routes_callers_at_once_all_return_the_same(rng):
    """Four threads route above-threshold inputs at once, as the plan
    build's chains do: none waits on another (joined under a time
    limit), and each gets the serial call's bytes."""
    import threading

    dst, hi = _route_tiles(rng, ROUTE_SIZES["ragged_tail"])
    want = [a.tobytes() for a in _one_serial_call(dst, hi)]
    got = [None] * 4

    def call(i):
        got[i] = [a.tobytes() for a in grr_routes_native(dst, hi)]

    threads = [threading.Thread(target=call, args=(i,), daemon=True)
               for i in range(len(got))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * len(got)


def test_grr_plan_coo_callers_at_once_all_return_the_serial_bytes(rng):
    """Four threads call the COO plan entry at once, as the plan
    build's chains do with their overflow levels: they share nothing,
    none waits on another (joined under a time limit), and each gets
    the bytes of a call made alone."""
    import threading

    from photon_ml_tpu.native import grr_plan_native_coo

    m, table_len, n_segments = 200_000, 5 * 16384, 30_000
    idx = rng.integers(0, table_len, m)
    seg = (n_segments * rng.random(m) ** 3.0).astype(np.int64)
    val = rng.normal(size=m).astype(np.float32)

    def plan():  # its bytes, and no clock's reading
        out = grr_plan_native_coo(idx, seg, val, table_len, n_segments, 8)
        return {name: a.tobytes() if isinstance(a, np.ndarray) else a
                for name, a in out.items() if not name.endswith("_s")}

    want = plan()
    assert want["n_st"] > 10 and len(want["spill_val"]) > 0
    got = [None] * 4

    def call(i):
        got[i] = plan()

    threads = [threading.Thread(target=call, args=(i,), daemon=True)
               for i in range(len(got))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * len(got)


# -- the subspace projection through the native library (ISSUE 36) --------------

def _shard(row_cols, rng):
    """``SparseRows`` of the given rows' columns with random values."""
    from photon_ml_tpu.data.sparse_rows import SparseRows

    indptr = np.zeros(len(row_cols) + 1, np.int64)
    np.cumsum([len(c) for c in row_cols], out=indptr[1:])
    cols = (np.concatenate([np.asarray(c, np.int32) for c in row_cols])
            if indptr[-1] else np.zeros(0, np.int32))
    return SparseRows(indptr=indptr, cols=cols.astype(np.int32),
                      vals=rng.normal(size=len(cols)).astype(np.float32))


def _power_law(rng, n, users, k, dim):
    """Entity ids and a shard of ``k`` ascending columns a row, both
    power-law: one entity in the widest bucket, thousands in the first."""
    user = (users * rng.random(n) ** 3.0).astype(np.int64) * 3 + 1
    cols = np.sort((dim * rng.random((n, k)) ** 2.2).astype(np.int64), axis=1)
    cols += np.arange(k) * dim              # ascending and distinct in a row
    return user, _shard(list(cols), rng), k * dim


def _project_case(case, rng):
    """(entity id per example, shard, global_dim, whether the grouping
    keeps ``example_entity``) of one case."""
    kept = True
    if case == "one_entity_in_the_last_of_several_buckets":
        user = np.concatenate([np.repeat(np.arange(40), 2),
                               np.repeat(np.arange(40, 50), 9),
                               np.full(70, 99)])
        rng.shuffle(user)
        rows = [np.unique(rng.integers(0, 50, rng.integers(1, 6)))
                for _ in user]
        dim = 50
    elif case == "an_entity_with_one_feature":
        user = np.array([4, 4, 4, 7, 7, 4, 9])
        rows = [[3], [3], [3], [0, 5], [5, 6], [3], [1, 2, 3]]
        dim = 8
    elif case == "entities_sharing_every_column":
        user = rng.integers(0, 9, 60)
        rows = [[2, 5, 11]] * 60
        dim = 12
    elif case == "a_column_at_global_dim_less_one":
        user = rng.integers(0, 6, 40)
        rows = [np.unique(np.append(rng.integers(0, 999, 2), 999))
                for _ in user]
        dim = 1000
    elif case == "rows_with_no_entries":
        user = np.array([1, 1, 2, 3, 3, 3, 3, 3, 5, 5])   # 2 and 5: no entry
        rows = [[0, 4], [], [], [1], [], [4, 6], [], [0], [], []]
        dim = 7
    elif case == "an_empty_shard":
        user = rng.integers(0, 5, 30)
        rows = [[]] * 30
        dim = 10
    elif case == "a_grouping_without_example_entity":
        user = (20 * rng.random(300) ** 2.5).astype(np.int64)
        rows = [np.unique(rng.integers(0, 40, 4)) for _ in user]
        dim, kept = 40, False
    elif case == "a_power_law_shard":
        user, shard, dim = _power_law(rng, 3000, 400, 5, 300)
        return user, shard, dim, kept
    else:
        raise AssertionError(case)
    return np.asarray(user), _shard(rows, rng), dim, kept


PROJECT_CASES = [
    "one_entity_in_the_last_of_several_buckets",
    "an_entity_with_one_feature", "entities_sharing_every_column",
    "a_column_at_global_dim_less_one", "rows_with_no_entries",
    "an_empty_shard", "a_grouping_without_example_entity",
    "a_power_law_shard"]


def _both_builders(monkeypatch, user, shard, dim, kept=True):
    """(the native builder's projection and blocks, the numpy body's)
    of one grouping."""
    import dataclasses

    import photon_ml_tpu.native as nat
    from photon_ml_tpu.game.dataset import group_by_entity
    from photon_ml_tpu.game.projector import build_subspace_projection

    grouping = group_by_entity(user, bucket_base=4)
    if not kept:
        grouping = dataclasses.replace(grouping, example_entity=None)
    native = build_subspace_projection(grouping, shard, dim)
    with monkeypatch.context() as patch:
        patch.setattr(nat, "lib", lambda: None)
        numpy = build_subspace_projection(grouping, shard, dim)
    assert (native[0].native, numpy[0].native, numpy[0].workers) == (1, 0, 1)
    return native, numpy


@pytest.fixture
def assert_the_same_bytes(sha256_of):
    """``feature_ids`` and ``x_blocks`` of two builds: equal leaf by
    leaf, in dtype and shape, and by one sha256 over all of them."""
    def check(got, want):
        got_leaves = got[0].feature_ids + got[1]
        want_leaves = want[0].feature_ids + want[1]
        assert len(got_leaves) == len(want_leaves)
        for a, b in zip(got_leaves, want_leaves):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
        assert sha256_of(got_leaves) == sha256_of(want_leaves)
    return check


@pytest.mark.parametrize("blocks", ["inline", "threaded"])
@pytest.mark.parametrize("case", PROJECT_CASES)
def test_native_projection_is_the_numpy_bodys_bytes(
        rng, monkeypatch, assert_the_same_bytes, case, blocks):
    """``feature_ids`` and ``x_blocks`` of the native builder against
    the numpy body's, leaf by leaf and by one sha256 over all of them:
    in one block on the calling thread, and cut into blocks of about
    eight entries on five threads."""
    import photon_ml_tpu.native as nat

    user, shard, dim, kept = _project_case(case, rng)
    if blocks == "threaded":
        monkeypatch.setattr(nat, "_PROJECT_BLOCK", 8)
        monkeypatch.setattr(nat, "_usable_cores", lambda: 5)
    native, numpy = _both_builders(monkeypatch, user, shard, dim, kept)
    assert_the_same_bytes(native, numpy)
    many = blocks == "threaded" and shard.nnz > 8
    assert native[0].workers == (min(5, -(-shard.nnz // 8)) if many else 1)
    if case == "one_entity_in_the_last_of_several_buckets":
        assert [len(f) for f in native[0].feature_ids] == [40, 10, 1]
    if case == "an_empty_shard":
        assert all(f.shape[1] == 1 and (f == -1).all()
                   for f in native[0].feature_ids)
        assert not any(b.any() for b in native[1])


def test_native_projection_on_one_worker_is_the_bytes_of_many(
        rng, monkeypatch, assert_the_same_bytes):
    """The same blocks of entities taken by one thread, in order, and by
    seven at once: one serial call's bytes."""
    import photon_ml_tpu.native as nat

    user, shard, dim = _power_law(rng, 3000, 400, 5, 300)
    monkeypatch.setattr(nat, "_PROJECT_BLOCK", 64)
    built = {}
    for workers in (1, 7):
        monkeypatch.setattr(nat, "_usable_cores", lambda: workers)
        built[workers], numpy = _both_builders(monkeypatch, user, shard, dim)
        assert built[workers][0].workers == workers
    assert_the_same_bytes(built[1], built[7])
    assert_the_same_bytes(built[1], numpy)


def test_native_projection_takes_its_threads_at_the_blocks_own_size(
        rng, monkeypatch, assert_the_same_bytes):
    """A power-law shard of four blocks of ``_PROJECT_BLOCK`` entries:
    the threaded branch as a fit takes it, nothing patched but the
    count of cores."""
    import photon_ml_tpu.native as nat

    n = 4 * nat._PROJECT_BLOCK // 5 - 100
    user, shard, dim = _power_law(rng, n, n // 8, 5, 2000)
    monkeypatch.setattr(nat, "_usable_cores", lambda: 6)
    native, numpy = _both_builders(monkeypatch, user, shard, dim)
    assert native[0].workers == 4
    assert len(native[1]) >= 4 and len(native[0].feature_ids[-1]) == 1
    assert_the_same_bytes(native, numpy)


def test_native_projection_refuses_what_would_write_outside_a_block(rng):
    """A rank past the last entity, a row past its entity's capacity
    and an ``indptr`` that is no CSR of the entries raise; none writes."""
    from photon_ml_tpu.native import re_project_native

    shard = _shard([[0, 1], [1], [2]], rng)
    good = dict(indptr=shard.indptr, cols=shard.cols, vals=shard.vals,
                ex_rank=np.array([0, 0, 1]), ex_pos=np.array([0, 1, 0]),
                bucket_start=np.array([0, 2]), capacities=[4])
    feature_ids, x_blocks, workers = re_project_native(**good)
    assert feature_ids[0].tolist() == [[0, 1], [2, -1]] and workers == 1
    assert x_blocks[0].shape == (2, 4, 2)
    for bad in (dict(ex_rank=np.array([0, 2, 1])),
                dict(ex_pos=np.array([0, 4, 0])),
                dict(indptr=np.array([0, 2, 1, 4])),
                dict(indptr=np.array([0, 2, 3, 5]))):
        with pytest.raises(ValueError, match="re_project_native"):
            re_project_native(**dict(good, **bad))


# -- the planner's hot split through the native library (ISSUE 40) -------------

def _split_case(case, rng):
    """(cols, vals, dim, the classes ``classify_columns`` gives them) of
    one case: an ELL batch whose columns follow a power law, column 0 in
    every row."""
    from photon_ml_tpu.data.grr import classify_columns

    n, k, dim, windows, hot_threshold = 600, 7, 900, 100, 64
    if case == "many_row_blocks":
        n = 5000
    cols = (dim * rng.random((n, k)) ** 3.0).astype(np.int32)
    cols[:, 0] = 0
    vals = rng.normal(size=(n, k)).astype(np.float32)
    if case == "no_tail":
        windows = 1     # four slots hold any column; one table window
    elif case == "explicit_zeros":
        vals[rng.random((n, k)) < 0.3] = 0.0
        vals[::7, 0] = -0.0
    elif case == "ell_padding_rows":
        cols[-40:], vals[-40:] = 0, 0.0
        cols[5, 3:], vals[5, 3:] = 0, 0.0
    elif case == "a_row_repeats_a_hot_column":
        cols = np.maximum(cols, 1)
        cols[:, 0] = cols[:, 1] = cols[:, 4] = 0
        vals[:, 0], vals[:, 1], vals[:, 4] = 1e8, 1.0, -1e8
    elif case == "zero_hot_columns":
        hot_threshold = 10 * n
    elif case == "every_column_hot":
        dim, windows, hot_threshold = 6, 1, 0
        cols = rng.integers(0, dim, (n, k)).astype(np.int32)
    elif case == "an_empty_batch":
        cols, vals = cols[:0], vals[:0]
    elif case not in ("a_tail_and_the_compact_remap", "many_row_blocks"):
        raise AssertionError(case)
    counts = np.bincount(cols[vals != 0].reshape(-1), minlength=dim)
    return cols, vals, dim, classify_columns(counts, windows,
                                             hot_threshold, max_hot=128)


SPLIT_CASES = ["no_tail", "a_tail_and_the_compact_remap", "explicit_zeros",
               "ell_padding_rows", "a_row_repeats_a_hot_column",
               "zero_hot_columns", "every_column_hot", "an_empty_batch",
               "many_row_blocks"]


def _split_leaves(split):
    """The arrays of ``_split_classes``' result, in order."""
    x_hot, (cols, vals, _width), tail = split
    return [x_hot, cols, vals] + list(tail or ())


def _both_splits(monkeypatch, cols, vals, dim, classes):
    """((counts, split, threads) of the native library, the numpy
    bodies' (counts, split))."""
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data import grr

    counts, count_threads = grr._column_counts(cols, vals, dim)
    split, split_threads = grr._split_classes_threads(
        cols, vals, dim, len(cols), classes)
    assert count_threads == split_threads >= 1
    with monkeypatch.context() as patch:
        patch.setattr(nat, "lib", lambda: None)
        numpy_counts, none = grr._column_counts(cols, vals, dim)
        numpy_split, no_threads = grr._split_classes_threads(
            cols, vals, dim, len(cols), classes)
    assert (none, no_threads) == (0, 0)
    return (counts, split, split_threads), (numpy_counts, numpy_split)


@pytest.fixture
def assert_the_same_split(sha256_of):
    """The counts and every array of two hot splits: equal in dtype,
    shape and bytes, and by one sha256 over all of them; the planned
    class's width and whether there is a tail alike."""
    def check(got, want):
        (got_counts, got_split), (want_counts, want_split) = got, want
        assert got_split[1][2] == want_split[1][2]
        assert (got_split[2] is None) == (want_split[2] is None)
        got_leaves = [got_counts] + _split_leaves(got_split)
        want_leaves = [want_counts] + _split_leaves(want_split)
        assert len(got_leaves) == len(want_leaves)
        for a, b in zip(got_leaves, want_leaves):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()
        assert sha256_of(got_leaves) == sha256_of(want_leaves)
    return check


@pytest.mark.parametrize("blocks", ["inline", "threaded"])
@pytest.mark.parametrize("case", SPLIT_CASES)
def test_native_hot_split_is_the_numpy_bodys_bytes(
        rng, monkeypatch, assert_the_same_split, case, blocks):
    """``column_counts_native`` against ``np.bincount`` and
    ``split_classes_native`` against ``_split_classes``' numpy body:
    the counts, ``x_hot``, the planned class's columns and values and
    the tail's three arrays, in one block on the calling thread and cut
    into blocks of 64 entries on five threads."""
    import photon_ml_tpu.native as nat

    cols, vals, dim, classes = _split_case(case, rng)
    if blocks == "threaded":
        monkeypatch.setattr(nat, "_SPLIT_BLOCK", 64)
        monkeypatch.setattr(nat, "_usable_cores", lambda: 5)
    (counts, split, threads), numpy = _both_splits(
        monkeypatch, cols, vals, dim, classes)
    assert_the_same_split((counts, split), numpy)
    assert threads == (5 if blocks == "threaded" and cols.size else 1)
    x_hot, (planned_cols, planned_vals, width), tail = split
    assert counts.dtype == np.int64 and counts.shape == (dim,)
    assert x_hot.shape == (len(cols), classes.hot.size)
    if case in ("no_tail", "every_column_hot"):
        assert classes.planned is None and tail is None
        assert planned_cols is cols and width == dim
    elif case != "an_empty_batch":
        assert classes.tail.size and len(tail[0]) == classes.tail_nnz
        assert width == classes.planned.size < dim
        assert np.count_nonzero(planned_vals) == classes.planned_nnz
    if case == "zero_hot_columns":
        assert x_hot.shape[1] == 0
    if case == "every_column_hot":
        assert not planned_vals.any()
    if case == "a_row_repeats_a_hot_column":
        # (1e8 + 1) - 1e8 in float32, in entry order: the 1 is lost
        assert classes.hot[0] == 0 and not x_hot[:, 0].any()
    if case == "ell_padding_rows":
        assert not x_hot[-40:].any() and not planned_vals[-40:].any()


def test_native_hot_split_on_one_worker_is_the_bytes_of_many(
        rng, monkeypatch, assert_the_same_split):
    """The same row blocks taken by one thread, in order, and by seven
    at once: the count's sums commute and every other output has its
    place, so both are the serial bytes."""
    import photon_ml_tpu.native as nat

    cols, vals, dim, classes = _split_case("many_row_blocks", rng)
    monkeypatch.setattr(nat, "_SPLIT_BLOCK", 256)
    built = {}
    for workers in (1, 7):
        monkeypatch.setattr(nat, "_usable_cores", lambda: workers)
        (counts, split, threads), numpy = _both_splits(
            monkeypatch, cols, vals, dim, classes)
        assert threads == workers
        built[workers] = (counts, split)
    assert_the_same_split(built[1], built[7])
    assert_the_same_split(built[1], numpy)


def test_native_hot_split_takes_its_threads_at_the_blocks_own_size(
        rng, monkeypatch, assert_the_same_split):
    """Nothing patched but the count of cores: a batch one entry under
    two blocks of ``_SPLIT_BLOCK`` runs inline, one of three blocks on
    three threads."""
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data.grr import classify_columns

    monkeypatch.setattr(nat, "_usable_cores", lambda: 6)
    k, dim = 8, 40000
    for n, workers in ((2 * nat._SPLIT_BLOCK // k - 1, 1),
                       (3 * nat._SPLIT_BLOCK // k, 3)):
        cols = (dim * rng.random((n, k)) ** 2.2).astype(np.int32)
        cols[:, 0] = 0
        vals = rng.normal(size=(n, k)).astype(np.float32)
        counts = np.bincount(cols.reshape(-1), minlength=dim)
        classes = classify_columns(counts, 200, 500, max_hot=128)
        assert classes.hot.size and classes.tail.size
        (counts, split, threads), numpy = _both_splits(
            monkeypatch, cols, vals, dim, classes)
        assert threads == workers
        assert_the_same_split((counts, split), numpy)


def test_native_hot_split_callers_at_once_all_return_the_serial_bytes(
        rng, monkeypatch, sha256_of):
    """Four threads count and split one batch at once, as the plan
    build's column chain counts beside the row part: they share nothing
    but their inputs, none waits on another (joined under a time limit),
    and each gets the bytes of a call made alone."""
    import threading

    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data import grr

    cols, vals, dim, classes = _split_case("many_row_blocks", rng)
    monkeypatch.setattr(nat, "_SPLIT_BLOCK", 512)
    monkeypatch.setattr(nat, "_usable_cores", lambda: 4)

    def once():
        return sha256_of(
            [grr._column_counts(cols, vals, dim)[0]]
            + _split_leaves(grr._split_classes(cols, vals, dim, len(cols),
                                               classes)))

    want = once()
    got = [None] * 4

    def call(i):
        got[i] = once()

    threads = [threading.Thread(target=call, args=(i,), daemon=True)
               for i in range(len(got))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * len(got)


@pytest.mark.parametrize("bad", [-1, 900, 2 ** 31 - 1])
@pytest.mark.parametrize("where", ["first_block", "last_block",
                                   "a_padding_entry"])
def test_native_hot_split_refuses_a_column_outside_the_table(
        rng, monkeypatch, where, bad):
    """A column id outside [0, dim) raises from either entry, in any
    block, and is not written through: the count leaves padding entries
    unread as ``np.bincount`` over the nonzeros does, the split reads
    every entry's class as ``code[cols]`` does."""
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.native import (
        column_counts_native,
        split_classes_native,
    )

    cols, vals, dim, classes = _split_case("many_row_blocks", rng)
    monkeypatch.setattr(nat, "_SPLIT_BLOCK", 512)
    monkeypatch.setattr(nat, "_usable_cores", lambda: 3)
    row = {"first_block": 2, "last_block": len(cols) - 1,
           "a_padding_entry": 700}[where]
    cols[row, 3] = bad
    code = np.zeros(dim, np.int32)
    if where == "a_padding_entry":
        vals[row, 3] = 0.0
        counts, _workers = column_counts_native(cols, vals, dim)
        assert counts.sum() == np.count_nonzero(vals)
    else:
        with pytest.raises(ValueError, match="column id out of range"):
            column_counts_native(cols, vals, dim)
    with pytest.raises(ValueError, match="column id out of range"):
        split_classes_native(cols, vals, code, len(cols), n_hot=0,
                             remap=True)
    # a class table that names a hot rank the block does not have
    cols[row, 3] = 1
    code[1] = -3
    vals[row, 3] = 1.0
    with pytest.raises(ValueError, match="hot rank out of range"):
        split_classes_native(cols, vals, code, len(cols), n_hot=2,
                             remap=True)


def test_native_column_counts_beyond_int32_raises_and_does_not_wrap():
    from photon_ml_tpu.native import column_counts_native

    cols = np.array([[0, 2 ** 32 + 1]], np.int64)
    with pytest.raises(ValueError, match="exceeds int32"):
        column_counts_native(cols, np.ones((1, 2), np.float32), 4)


def test_native_hot_split_says_so_where_its_outputs_cannot_be_allocated(
        rng, capfd):
    """A table no machine holds: the wrapper says so on stderr and
    returns None, and the caller's numpy body decides."""
    from photon_ml_tpu.native import column_counts_native

    cols, vals, _dim, _classes = _split_case("no_tail", rng)
    assert column_counts_native(cols, vals, 10 ** 15) is None
    assert "column_counts_native: no memory" in capfd.readouterr().err


# -- the GRR plan's fill on native threads ------------------------------------

PLAN_SIZES = ("cap", "n_gw", "n_ow", "n_st")
PLAN_LEAVES = ("hi", "vals", "dst", "gw_of_st", "ow_of_st", "first_of_ow",
               "spill_idx", "spill_seg", "spill_val")


def _skewed_ell(rng, n, k, dim):
    """``n`` rows of ``k`` columns, most of them in the first table
    window (cells of value zero among them), so small caps spill."""
    cols = np.minimum(300 * np.exp(rng.uniform(size=(n, k))
                                   * np.log((dim + 300) / 300)) - 300,
                      dim - 1).astype(np.int32)
    vals = rng.normal(size=(n, k)).astype(np.float32)
    vals[rng.random((n, k)) < 0.1] = 0.0
    return cols, vals


def _plan_case(case, rng):
    """(the native builder's call, its entries as COO in scan order,
    table_len, n_segments, cap, the bound on supertiles): the ELL cells
    scan row by row, so direction 0's segments arrive in order and
    direction 1's do not."""
    from photon_ml_tpu.native import grr_plan_native, grr_plan_native_coo

    bound = None
    if case.startswith("ell"):
        direction = int(case.startswith("ell_columns"))
        n, k, dim = (30000, 6, 40000) if direction == 0 else (20000, 6, 30000)
        cols, vals = _skewed_ell(rng, n, k, dim)
        cap = None if case.endswith("chosen_cap") else 4
        rows = np.repeat(np.arange(n), k)
        idx, seg = ((cols.ravel(), rows) if direction == 0
                    else (rows, cols.ravel()))
        table_len, n_segments = (dim, n) if direction == 0 else (n, dim)
        part = (16384, 2 * 16384) if case == "ell_rows_an_idx_range" \
            else None
        lo, hi = part or (0, table_len)
        keep = (vals.ravel() != 0) & (idx >= lo) & (idx < hi)
        coo = (idx[keep] - lo, seg[keep], vals.ravel()[keep])

        def build():
            return grr_plan_native(cols, vals, direction, table_len,
                                   n_segments, cap, idx_range=part)
        return build, coo, hi - lo, n_segments, cap, bound
    table_len, n_segments, cap = 5 * 16384, 30000, 8
    m = 150000
    idx = rng.integers(0, table_len, m)
    seg = (n_segments * rng.random(m) ** 3.0).astype(np.int64)
    if case == "coo_sorted":
        order = np.lexsort((idx // 16384, seg))
        idx, seg = idx[order], seg[order]
    elif case == "coo_fewer_windows_than_workers":
        n_segments = 3 * (16384 // cap) - 5        # three output windows
        seg = seg % n_segments
    elif case == "coo_over_the_bound":
        bound = 10
    elif case != "coo_unsorted":
        raise AssertionError(case)
    val = rng.normal(size=m).astype(np.float32)
    val[rng.random(m) < 0.05] = 0.0

    def build():
        return grr_plan_native_coo(idx, seg, val, table_len, n_segments, cap,
                                   max_supertiles=bound)
    keep = val != 0
    return build, (idx[keep], seg[keep], val[keep]), table_len, n_segments, \
        cap, bound


PLAN_CASES = ["ell_rows_as_segments", "ell_rows_an_idx_range",
              "ell_rows_chosen_cap", "ell_columns_as_segments",
              "ell_columns_chosen_cap", "coo_sorted", "coo_unsorted",
              "coo_fewer_windows_than_workers", "coo_over_the_bound"]


@pytest.mark.parametrize("case", PLAN_CASES)
def test_grr_plan_fill_on_any_thread_count_is_the_numpy_bodys_bytes(
        rng, monkeypatch, sha256_of, case):
    """The plan's fill on one output window a block: at 1, 2, 7 and 33
    usable cores (more threads than this host's cores where the plan has
    the windows for them) every size and leaf, the spill's in scan order
    among them, is the same, one sha256 over all, and the numpy body's
    at the cap the C++ took; the fill ran on min(cores, windows)
    threads, inline under two; a plan over its bound is its sizes
    alone, never filled."""
    import photon_ml_tpu.native as nat
    from photon_ml_tpu.data import grr

    build, (idx, seg, val), table_len, n_segments, cap, bound = \
        _plan_case(case, rng)
    digests = set()
    for cores in (1, 2, 7, 33):
        monkeypatch.setattr(nat, "_usable_cores", lambda: cores)
        plan = build()
        n_ow = plan["n_ow"]
        if bound is not None:
            assert plan["n_st"] > bound and "vals" not in plan
            assert (plan["workers"], plan["fill_s"]) == (0, 0.0)
        else:
            assert plan["workers"] == min(cores, n_ow)
            digests.add(sha256_of([plan[name] for name in PLAN_LEAVES]))
        sizes = [plan[name] for name in PLAN_SIZES]
    if case == "coo_fewer_windows_than_workers":
        assert n_ow == 3
    elif bound is None:
        assert n_ow >= 4 and plan["workers"] == min(33, n_ow) >= 7
    if case.endswith("chosen_cap"):
        assert cap is None and plan["cap"] in (4, 8, 16, 32, 64)
    n_gw = -(-table_len // 16384)
    numpy = grr._plan_coo_numpy(idx, seg, val, n_gw, n_ow, sizes[0], bound)
    assert [numpy[name] for name in PLAN_SIZES] == sizes
    if bound is not None:
        return
    assert len(digests) == 1 and np.count_nonzero(plan["spill_val"])
    for name in PLAN_LEAVES:
        assert plan[name].dtype == numpy[name].dtype
        assert plan[name].shape == numpy[name].shape
        assert plan[name].tobytes() == numpy[name].tobytes(), name
    assert digests == {sha256_of([numpy[name] for name in PLAN_LEAVES])}
