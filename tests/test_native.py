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

    def plan():
        out = grr_plan_native_coo(idx, seg, val, table_len, n_segments, 8)
        return {name: a.tobytes() if isinstance(a, np.ndarray) else a
                for name, a in out.items()}

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
