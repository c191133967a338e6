"""Native C++ ETL bindings: compile-on-first-use, ctypes, numpy fallback.

Reference counterpart: the JVM data plane (Spark executors deserializing
Avro, shuffling, building per-partition iterables — SURVEY.md §5.8).
The rebuild's data plane is host-side array construction; the hot parts
(LIBSVM text parsing, the transposed-ELL counting sort, the planner's
column count and class split, the GRR plans and their routes, the random
effects' subspace projection) live in ``fast_etl.cpp`` and are bound
here.

Build model: ``g++ -O3 -shared -fPIC -pthread`` on first use (seconds,
once; the GRR plans' fill, their router, the planner's hot split and
the projection run their blocks on ``std::thread``s) into
a .so next to the source whose name carries a hash of the source and the
build command, so "is the binary stale" is decided from content, not
from mtimes a copy does not keep.  The build is portable (no
``-march=native``): a tree copied to another machine may carry the .so.
Every caller treats ``lib()`` returning None as "no native library" and
falls back to the numpy implementation, so the framework works on
machines with no toolchain; the reason goes to stderr, and
``chip_smoke.py`` treats None as a failure.  ``PHOTON_ML_TPU_NATIVE=0``
forces the fallback (comparisons, debugging).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import threading
import time

import numpy as np

from photon_ml_tpu import telemetry

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fast_etl.cpp")
_BUILD_CMD = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]
_SO_PREFIX = os.path.join(
    _HERE, f"_fast_etl_{sys.implementation.cache_tag}")

_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = False  # False = not yet attempted


def _so_path() -> str:
    h = hashlib.sha256(" ".join(_BUILD_CMD).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return f"{_SO_PREFIX}_{h.hexdigest()[:16]}.so"


def _build(so: str) -> bool:
    # Build beside the target and rename into place: another process
    # (a test worker, a fleet host) may be loading the same path.
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(
            _BUILD_CMD + [_SRC, "-o", tmp],
            capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        sys.stderr.write(
            f"photon_ml_tpu.native: g++ unavailable ({e!r}); using the "
            "numpy fallbacks (GRR plan compilation will be much slower)\n"
        )
        return False
    if proc.returncode != 0:
        sys.stderr.write(
            f"photon_ml_tpu.native: build failed, using numpy fallback\n"
            f"{proc.stderr[:2000]}\n"
        )
        return False
    os.replace(tmp, so)
    for stale in glob.glob(f"{_SO_PREFIX}*.so"):
        if stale != so:
            os.remove(stale)
    return True


def lib() -> "ctypes.CDLL | None":
    """The loaded native library, or None (fallback path)."""
    global _lib
    if _lib is not False:
        return _lib  # type: ignore[return-value]
    from photon_ml_tpu.config import read_env

    with _lock:
        if _lib is not False:
            return _lib  # type: ignore[return-value]
        if read_env("PHOTON_ML_TPU_NATIVE") == "0":
            _lib = None
            return None
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _lib = None
            return None
        try:
            dll = ctypes.CDLL(so)
        except OSError as e:
            sys.stderr.write(
                f"photon_ml_tpu.native: cannot load {so} ({e!r}); using "
                "the numpy fallbacks\n")
            _lib = None
            return None
        dll.pml_libsvm_parse.restype = ctypes.c_void_p
        dll.pml_libsvm_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        dll.pml_libsvm_sizes.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
        ]
        dll.pml_libsvm_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.pml_libsvm_free.argtypes = [ctypes.c_void_p]
        dll.pml_colmajor_vrows.restype = ctypes.c_int64
        dll.pml_colmajor_vrows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
        dll.pml_colmajor_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        dll.pml_edge_color.restype = ctypes.c_int32
        dll.pml_edge_color.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
        ]
        dll.pml_grr_routes.restype = ctypes.c_int32
        dll.pml_grr_routes.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        dll.pml_grr_routes_blocks.restype = ctypes.c_int32
        dll.pml_grr_routes_blocks.argtypes = (
            dll.pml_grr_routes.argtypes + [ctypes.c_int64, ctypes.c_int32])
        dll.pml_grr_plan.restype = ctypes.c_void_p
        dll.pml_grr_plan.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
            ctypes.c_int64,
        ]
        dll.pml_grr_plan_coo.restype = ctypes.c_void_p
        dll.pml_grr_plan_coo.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int64,
        ]
        dll.pml_grr_plan_sizes.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        dll.pml_grr_plan_fill.restype = ctypes.c_int32
        dll.pml_grr_plan_fill.argtypes = [ctypes.c_void_p] + [
            ctypes.c_void_p] * 9 + [ctypes.c_int32]
        dll.pml_grr_plan_free.argtypes = [ctypes.c_void_p]
        dll.pml_re_project_widths.restype = ctypes.c_int32
        dll.pml_re_project_widths.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ]
        dll.pml_re_project_fill.restype = ctypes.c_int32
        dll.pml_re_project_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32,
        ]
        dll.pml_column_counts.restype = ctypes.c_int32
        dll.pml_column_counts.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32,
        ]
        dll.pml_split_classes_sizes.restype = ctypes.c_int32
        dll.pml_split_classes_sizes.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ]
        dll.pml_split_classes_fill.restype = ctypes.c_int32
        dll.pml_split_classes_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p,
        ] + [ctypes.c_void_p] * 6
        _lib = dll
        return dll


def native_available() -> bool:
    """True when the native library is loaded (or loadable)."""
    return lib() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def libsvm_parse_native(data: bytes):
    """Parse LIBSVM text → (labels, row_ptr, cols, vals, max_col), or
    None if the native library is unavailable.  Raises ValueError on
    malformed input (same contract as the Python parser)."""
    dll = lib()
    if dll is None:
        return None
    handle = dll.pml_libsvm_parse(data, len(data))
    if not handle:
        raise ValueError("malformed LIBSVM input (native parser)")
    try:
        n = ctypes.c_int64()
        nnz = ctypes.c_int64()
        max_col = ctypes.c_int32()
        dll.pml_libsvm_sizes(handle, ctypes.byref(n), ctypes.byref(nnz),
                             ctypes.byref(max_col))
        labels = np.empty(n.value, np.float32)
        row_ptr = np.empty(n.value + 1, np.int64)
        cols = np.empty(nnz.value, np.int32)
        vals = np.empty(nnz.value, np.float32)
        dll.pml_libsvm_fill(handle, _ptr(labels), _ptr(row_ptr),
                            _ptr(cols), _ptr(vals))
        return labels, row_ptr, cols, vals, int(max_col.value)
    finally:
        dll.pml_libsvm_free(handle)


def edge_color_native(
    src: np.ndarray, dst: np.ndarray, n_left: int, n_right: int,
    n_colors: int,
) -> "np.ndarray | None":
    """Proper edge coloring of a bipartite multigraph (Euler split).

    Every vertex's degree must be divisible by ``n_colors`` (a power of
    two).  Returns int32 colors per edge, or None when the native
    library is unavailable (callers fall back to the Python colorer in
    ``ops.crossbar``)."""
    dll = lib()
    if dll is None:
        return None
    src = np.ascontiguousarray(src, np.int32)
    dst = np.ascontiguousarray(dst, np.int32)
    color = np.empty(src.size, np.int32)
    rc = dll.pml_edge_color(_ptr(src), _ptr(dst), src.size, n_left,
                            n_right, n_colors, _ptr(color))
    if rc != 0:
        raise ValueError("pml_edge_color: invalid arguments")
    return color


# Supertiles a routing block holds: about 40 ms of colouring, small
# enough that the last round of a call, or two plan-build chains
# routing at once, leave no core idle for long (8 threads on 778
# tiles: 0.29 s at 16, 0.37 s at 64).  Not a setting: what adapts is
# the number of blocks.
_ROUTE_BLOCK = 16


def _usable_cores() -> int:
    """Cores this process may run on (its affinity mask where the
    platform has one)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


def grr_routes_native(dst: np.ndarray, hi: np.ndarray):
    """Batched GRR supertile routing → (g1, g2, g3) int8 arrays, or None
    when the native library is unavailable (Python fallback in
    ``data.grr``).  ``dst``: [n_st,128,128] int32 slot bijections;
    ``hi``: [n_st,128,128] int8 gather planes.  Raises ValueError if a
    tile is not a bijection.

    Supertiles share nothing, so from two blocks of ``_ROUTE_BLOCK``
    on the call colours its blocks on every core the process may use
    (native threads that live for the call and write their slices of
    the three outputs in place: the bytes are those of one serial
    call), inside a ``grr_routes`` stage on the calling thread.  A
    smaller call runs inline."""
    dll = lib()
    if dll is None:
        return None
    dst = np.ascontiguousarray(dst, np.int32)
    hi = np.ascontiguousarray(hi, np.int8)
    n_st = dst.shape[0]
    g1 = np.empty_like(hi)
    g2 = np.empty_like(hi)
    g3 = np.empty_like(hi)
    args = (_ptr(dst), _ptr(hi), n_st, _ptr(g1), _ptr(g2), _ptr(g3))
    blocks = -(-n_st // _ROUTE_BLOCK)
    if blocks < 2:
        rc = dll.pml_grr_routes(*args)
    else:
        workers = min(_usable_cores(), blocks)
        with telemetry.stage("grr_routes", supertiles=n_st, blocks=blocks,
                             workers=workers):
            rc = dll.pml_grr_routes_blocks(*args, _ROUTE_BLOCK, workers)
    if rc != 0:
        raise ValueError("pml_grr_routes: dst tile is not a bijection")
    return g1, g2, g3


def colmajor_build_native(
    cols: np.ndarray,
    vals: np.ndarray,
    dim: int,
    capacity: int,
    pad_vrows_to_multiple: int | None = None,
    pad_vrows_to: int | None = None,
):
    """Transposed-ELL build → (tvals, trows, vcol) or None (no native).

    Same semantics as the numpy path in ``data.colmajor.build_colmajor``
    except entry order within a column follows row-scan order (both are
    valid orderings of the same multiset; sums agree).
    """
    dll = lib()
    if dll is None:
        return None
    n, k = cols.shape
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    counts = np.zeros(dim, np.int64)
    v = dll.pml_colmajor_vrows(_ptr(cols), _ptr(vals), n, k, dim,
                               capacity, _ptr(counts))
    if v < 0:
        raise ValueError("column id out of range in colmajor build")
    from photon_ml_tpu.ops.kernels import vrow_pad

    v_pad = vrow_pad(int(v), pad_vrows_to_multiple)
    if pad_vrows_to is not None:
        if pad_vrows_to < v:
            raise ValueError(f"pad_vrows_to={pad_vrows_to} < V={v}")
        v_pad = pad_vrows_to
    tvals = np.zeros((v_pad, capacity), np.float32)
    trows = np.zeros((v_pad, capacity), np.int32)
    vcol = np.zeros(v_pad, np.int32)
    dll.pml_colmajor_fill(_ptr(cols), _ptr(vals), n, k, dim, capacity,
                          _ptr(counts), v_pad, _ptr(tvals), _ptr(trows),
                          _ptr(vcol))
    return tvals, trows, vcol


def _int32_ids(ids, what: str) -> np.ndarray:
    """``ids`` as contiguous int32.  The narrowing must not wrap
    (advisor finding: a wrapped 64-bit id landing back inside the range
    would pass the C++ range check and yield a silently wrong plan)."""
    ids = np.asarray(ids)
    if ids.dtype != np.int32 and ids.size and (
        int(ids.max()) > np.iinfo(np.int32).max
        or int(ids.min()) < np.iinfo(np.int32).min
    ):
        raise ValueError(f"{what} id exceeds int32 range in GRR plan build")
    return np.ascontiguousarray(ids, np.int32)


def _check_cap(cap: int) -> None:
    """A cap that is no power of two makes distinct (rank, segment)
    pairs collide on one final slot (same contract as the numpy path)."""
    if cap not in (1, 2, 4, 8, 16, 32, 64, 128):
        raise ValueError(f"cap must be a power of two ≤ 128, got {cap}")


def _plan_workers(n_ow: int) -> int:
    """Threads a plan's fill runs on, the caller's included: one output
    window a block, so ``min(_usable_cores(), n_ow)``, and 1 (inline)
    under two windows.

    Not a setting: one window is the block because a block costs
    nothing of its own but a counter step and the occupancy bitmaps of
    its supertiles, and a window's supertiles (one a table window with
    entries, ``n_gw`` at most) are already 147 KB of slots each, so the
    smallest block balances the threads' last round best."""
    return min(_usable_cores(), max(1, int(n_ow)))


def _read_grr_plan(dll, start):
    """The plan of the head call ``start()`` (``pml_grr_plan`` or
    ``pml_grr_plan_coo``, which returns a handle) as a dict, and the
    handle freed.

    The head counts: pass A, the cap, pass B and the block list, on
    this thread.  The slot arrays are then allocated here, uninitialised,
    and ``pml_grr_plan_fill`` writes every element of them and of the
    spill's arrays in place, on ``workers`` threads (``_plan_workers``:
    native threads that live for the call, one output window a block;
    the bytes are those of one serial pass), so their pages are first
    touched by the threads that fill them.

    The dict: the plan arrays (hi/vals/dst per supertile, block maps,
    spill COO) with ``cap``, ``n_gw``, ``n_ow``, ``n_st``, ``workers``
    and the seconds of the two calls, ``count_s`` and ``fill_s``; the
    sizes and ``count_s`` alone (``workers`` 0, ``fill_s`` 0) where the
    plan was over its caller's bound; None where the C++ declined (size
    overflow, a shape it does not take, no memory)."""
    t0 = time.perf_counter()
    handle = start()
    if not handle:
        raise MemoryError("pml_grr_plan allocation failed")
    try:
        n_st = ctypes.c_int64()
        n_spill = ctypes.c_int64()
        cap_out = ctypes.c_int32()
        n_gw = ctypes.c_int32()
        n_ow = ctypes.c_int32()
        error = ctypes.c_int32()
        dll.pml_grr_plan_sizes(
            handle, ctypes.byref(n_st), ctypes.byref(n_spill),
            ctypes.byref(cap_out), ctypes.byref(n_gw), ctypes.byref(n_ow),
            ctypes.byref(error),
        )
        t1 = time.perf_counter()
        if error.value == 1:
            raise ValueError("idx or seg out of range in GRR plan build")
        st = int(n_st.value)
        sizes = {"cap": int(cap_out.value), "n_gw": int(n_gw.value),
                 "n_ow": int(n_ow.value), "n_st": st}
        if error.value == 4:
            return dict(sizes, workers=0, count_s=round(t1 - t0, 4),
                        fill_s=0.0)
        if error.value:
            return None  # the numpy path decides
        m = int(n_spill.value)
        workers = _plan_workers(sizes["n_ow"])
        out = dict(
            hi=np.empty((st, 128, 128), np.int8),
            vals=np.empty((st, 128, 128), np.float32),
            dst=np.empty((st, 128, 128), np.int32),
            gw_of_st=np.empty(st, np.int32), ow_of_st=np.empty(st, np.int32),
            first_of_ow=np.empty(st, np.int32),
            spill_idx=np.empty(m, np.int32), spill_seg=np.empty(m, np.int32),
            spill_val=np.empty(m, np.float32))
        rc = dll.pml_grr_plan_fill(handle, *map(_ptr, out.values()), workers)
    finally:
        dll.pml_grr_plan_free(handle)
    if rc == -2:
        return _no_room("pml_grr_plan_fill")
    if rc != 0:
        raise RuntimeError("pml_grr_plan_fill: the spill disagrees with "
                           "the head's count")
    return dict(sizes, **out, workers=workers, count_s=round(t1 - t0, 4),
                fill_s=round(time.perf_counter() - t1, 4))


def grr_plan_native(
    cols: np.ndarray,
    vals: np.ndarray,
    direction: int,
    table_len: int,
    n_segments: int,
    cap: int | None = None,
    idx_range: "tuple[int, int] | None" = None,
):
    """One GRR direction's plan straight from the row-ELL arrays, or
    None when the native library is unavailable (numpy path in
    ``data.grr.build_grr_direction``).

    ``direction`` 0: idx=column, seg=row (the margins X·w direction);
    1: idx=row, seg=column (the gradient Xᵀr direction).  Entries with
    value 0 are dropped (zero the hot-column entries before calling).
    ``idx_range=(lo, hi)`` restricts the plan to table indices in
    [lo, hi) — entries outside are skipped (they belong to a sibling
    column-range sub-plan), indices are rebased to idx-lo, and the
    returned plan's table axis is [0, hi-lo); ``lo`` must be
    window-aligned (a multiple of 16384).  Indices outside
    [0, table_len) are still an error.
    Returns ``_read_grr_plan``'s dict with the chosen cap: the C++
    head counts on this thread, the fill writes the plan's arrays on
    every core the process may use from two output windows on.  Route
    coloring is the caller's next step (``grr_routes_native``).
    """
    dll = lib()
    if dll is None:
        return None
    cols = _int32_ids(cols, "column")
    vals = np.ascontiguousarray(vals, np.float32)
    n, k = cols.shape
    # cap=0 is rejected; only None means "choose from occupancy".
    if cap is not None:
        _check_cap(cap)
    lo, hi = idx_range if idx_range is not None else (0, int(table_len))
    return _read_grr_plan(dll, lambda: dll.pml_grr_plan(
        _ptr(cols), _ptr(vals), n, k, int(direction), int(table_len),
        int(n_segments), 0 if cap is None else int(cap), int(lo), int(hi),
    ))


def grr_plan_native_coo(
    idx: np.ndarray,
    seg: np.ndarray,
    val: np.ndarray,
    table_len: int,
    n_segments: int,
    cap: int,
    max_supertiles: "int | None" = None,
):
    """The same plan from COO entries in the order given (the spill of
    the level above, a mid split), or None when the native library is
    unavailable or declines.  ``cap`` is the caller's to resolve
    (``data.grr``'s sampled heuristic): at one cap these are the bytes
    the numpy body writes.  Entries with value 0 are dropped; an idx or
    seg out of range raises.  Past ``max_supertiles`` (None: no bound)
    the C++ stops once the supertiles are counted and the dict holds
    the sizes alone.  Head and fill as ``grr_plan_native``'s."""
    dll = lib()
    if dll is None:
        return None
    _check_cap(cap)
    idx = _int32_ids(idx, "idx")
    seg = _int32_ids(seg, "seg")
    val = np.ascontiguousarray(val, np.float32)
    if not idx.shape == seg.shape == val.shape or idx.ndim != 1:
        raise ValueError("idx, seg and val must be 1-D and of one length")
    return _read_grr_plan(dll, lambda: dll.pml_grr_plan_coo(
        _ptr(idx), _ptr(seg), _ptr(val), idx.size, int(table_len),
        int(n_segments), int(cap),
        -1 if max_supertiles is None else int(max_supertiles),
    ))


# Entries a projection block holds, about: an entity is never cut, so
# a block runs over by its last entity's.  Small enough that the cell's
# 28.7 million entries are 440 blocks for a dozen cores, large enough
# that a block's counter step is nothing.  Not a setting: what adapts
# is the number of blocks.
_PROJECT_BLOCK = 1 << 16


def re_project_native(
    indptr: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    ex_rank: np.ndarray,
    ex_pos: np.ndarray,
    bucket_start: np.ndarray,
    capacities,
):
    """Per-entity subspaces and their dense blocks from a shard's CSR →
    ``(feature_ids, x_blocks, workers)``, or None when the native
    library is unavailable or out of memory (numpy body in
    ``game.projector``, whose bytes these are).

    ``ex_rank``: each example's entity as its rank in (bucket, slot)
    order; ``ex_pos``: the example's row within its entity;
    ``bucket_start`` [n_buckets + 1]: the buckets' first ranks;
    ``capacities``: rows an entity of each bucket holds.  Bucket ``b``
    comes back as ``feature_ids[b]`` int32 [entities, p_b] (an entity's
    distinct columns ascending, −1 padded; p_b the bucket's widest
    subspace, at least 1) and ``x_blocks[b]`` float32 [entities,
    capacity, p_b].

    Entities share nothing, so from two blocks of ``_PROJECT_BLOCK``
    entries on both passes run on every core the process may use
    (``workers``; native threads that live for a call and write
    disjoint rows and slabs in place: the bytes are those of one serial
    call), and the blocks' zero pages are first touched there.  A
    smaller call runs inline (``workers`` 1)."""
    dll = lib()
    if dll is None:
        return None
    indptr = np.ascontiguousarray(indptr, np.int64)
    cols = _int32_ids(cols, "column")
    vals = np.ascontiguousarray(vals, np.float32)
    ex_rank = np.ascontiguousarray(ex_rank, np.int64)
    ex_pos = np.ascontiguousarray(ex_pos, np.int64)
    bucket_start = np.ascontiguousarray(bucket_start, np.int64)
    capacity = np.ascontiguousarray(capacities, np.int64)
    n = len(indptr) - 1
    n_buckets = len(capacity)
    n_entities = int(bucket_start[-1])
    if not (len(ex_rank) == len(ex_pos) == n and len(cols) == len(vals)
            and len(bucket_start) == n_buckets + 1):
        raise ValueError("re_project_native: array lengths disagree")
    workers = min(_usable_cores(),
                  max(1, -(-int(indptr[-1]) // _PROJECT_BLOCK)))
    ex_start = np.empty(n_entities + 1, np.int64)
    ex_order = np.empty(n, np.int64)
    ent_cum = np.empty(n_entities + 1, np.int64)
    width = np.empty(n_entities, np.int32)
    rc = dll.pml_re_project_widths(
        _ptr(indptr), _ptr(cols), n, len(cols), _ptr(ex_rank), n_entities,
        _ptr(ex_start), _ptr(ex_order), _ptr(ent_cum), _ptr(width),
        _PROJECT_BLOCK, workers)
    if rc == -2:
        return None
    if rc != 0:
        raise ValueError("re_project_native: indptr is not the CSR of its "
                         "entries, or an entity rank is out of range")
    p = np.ones(n_buckets, np.int64)
    feature_ids, x_blocks = [], []
    for b in range(n_buckets):
        lo, hi = int(bucket_start[b]), int(bucket_start[b + 1])
        if hi > lo:
            p[b] = max(int(width[lo:hi].max()), 1)
        feature_ids.append(np.full((hi - lo, p[b]), -1, np.int32))
        # zero pages nobody has touched: the fill's threads do
        x_blocks.append(np.zeros((hi - lo, int(capacity[b]), p[b]),
                                 np.float32))
    pointers = ctypes.c_void_p * n_buckets
    rc = dll.pml_re_project_fill(
        _ptr(indptr), _ptr(cols), _ptr(vals), _ptr(ex_pos), n_entities,
        _ptr(ex_start), _ptr(ex_order), _ptr(ent_cum), n_buckets,
        _ptr(bucket_start), _ptr(capacity), _ptr(p),
        pointers(*(a.ctypes.data for a in feature_ids)),
        pointers(*(a.ctypes.data for a in x_blocks)),
        _PROJECT_BLOCK, workers)
    if rc == -2:
        return None
    if rc != 0:
        raise ValueError("re_project_native: an example's row is outside "
                         "its entity's capacity")
    return feature_ids, x_blocks, workers


# Entries a block of the planner's hot split holds (the class split
# rounds it down to whole rows): 38 million entries are 583 blocks for
# a dozen cores, and a block's private count cache (4,096 slots) is
# flushed once for every sixteen entries at most.  Not a setting: what
# adapts is the number of blocks.
_SPLIT_BLOCK = 1 << 16


def _ell_pair(cols, vals):
    """An ELL batch's ``cols`` and ``vals`` as the library reads them:
    contiguous int32 and float32 of one 2-D shape."""
    cols = _int32_ids(cols, "column")
    vals = np.ascontiguousarray(vals, np.float32)
    if cols.ndim != 2 or cols.shape != vals.shape:
        raise ValueError("cols and vals must be 2-D and of one shape")
    return cols, vals


def _no_room(entry: str) -> None:
    """An entry's outputs could not be allocated: said on stderr, as
    the library's other reasons are, and the caller's numpy body
    decides (it may fail the same way, with its own traceback)."""
    sys.stderr.write(f"photon_ml_tpu.native: {entry}: no memory for the "
                     "outputs; using the numpy body\n")


def _split_workers(entries: int) -> int:
    """Threads a pass of the hot split over ``entries`` ELL slots runs
    on, the caller's included: 1 (inline) under two blocks."""
    return min(_usable_cores(), max(1, entries // _SPLIT_BLOCK))


def column_counts_native(cols: np.ndarray, vals: np.ndarray, dim: int):
    """``(np.bincount(cols[vals != 0].reshape(-1), minlength=dim),
    workers)`` of an ELL batch, or None when the native library is
    unavailable or the table cannot be allocated (the caller's
    ``np.bincount`` decides).  Raises ValueError at a column outside
    [0, dim), where ``np.bincount`` returns a longer table or raises.

    Row blocks of ``_SPLIT_BLOCK`` entries on every core the process
    may use (``workers``; native threads that live for the call), each
    counting in a small private cache it adds into the one shared
    table: integer sums commute, so the bytes are the serial count's,
    and the table's zero pages are first touched there.  An input under
    two blocks runs inline (``workers`` 1)."""
    dll = lib()
    if dll is None:
        return None
    cols, vals = _ell_pair(cols, vals)
    workers = _split_workers(cols.size)
    try:
        counts = np.zeros(int(dim), np.int64)
    except MemoryError:
        return _no_room("column_counts_native")
    rc = dll.pml_column_counts(_ptr(cols), _ptr(vals), cols.size, int(dim),
                               _ptr(counts), _SPLIT_BLOCK, workers)
    if rc != 0:
        raise ValueError("column_counts_native: column id out of range")
    return counts, workers


def split_classes_native(cols: np.ndarray, vals: np.ndarray,
                         code: np.ndarray, n_rows: int, n_hot: int,
                         remap: bool):
    """An ELL batch taken apart by column class (``data.grr.
    _split_classes``, whose bytes these are) → ``(x_hot, cols_planned,
    vals_planned, (tail_row, tail_col, tail_val), workers)``, or None
    when the native library is unavailable or an output cannot be
    allocated (the numpy body decides).

    ``code`` [dim] int32: a planned column's id in the plans (>= 0), a
    hot column's ``-1 - rank``, a tail column's ``INT32_MIN``.
    ``x_hot`` [n_rows, n_hot] float32: a row's hot entries added in
    entry order.  ``cols_planned`` [n, k] int32: ``max(code[cols], 0)``
    with ``remap``, else None (the plans keep ``cols``).
    ``vals_planned`` [n, k] float32: a planned column's entry keeps its
    value, every other is 0.0.  The tail's entries (nonzero value, tail
    column) as int32 rows, int32 columns and float32 values in row
    order, then entry order; empty where there is none.  Raises
    ValueError at a column outside [0, dim) or a hot rank outside
    [0, n_hot).

    Two passes over row blocks of ``_SPLIT_BLOCK`` entries on every
    core the process may use (``workers``): the first counts each
    block's tail entries, which places every block in the tail's
    arrays; the second writes every output in place, so its pages are
    first touched by the threads that fill them.  An input under two
    blocks runs inline (``workers`` 1)."""
    dll = lib()
    if dll is None:
        return None
    cols, vals = _ell_pair(cols, vals)
    code = np.ascontiguousarray(code, np.int32)
    n, k = cols.shape
    if n_rows < n:
        raise ValueError("split_classes_native: n_rows is under the "
                         "batch's rows")
    block_rows = max(1, _SPLIT_BLOCK // max(k, 1))
    workers = _split_workers(cols.size)
    tail_start = np.empty(-(-n // block_rows) + 1, np.int64)
    rc = dll.pml_split_classes_sizes(
        _ptr(cols), _ptr(vals), n, k, _ptr(code), code.size, block_rows,
        workers, _ptr(tail_start))
    if rc != 0:
        raise ValueError("split_classes_native: column id out of range")
    n_tail = int(tail_start[-1])
    try:
        # zero pages nobody has touched: the fill's threads do
        x_hot = np.zeros((int(n_rows), int(n_hot)), np.float32)
        cols_planned = np.empty((n, k), np.int32) if remap else None
        vals_planned = np.empty((n, k), np.float32)
        tail = (np.empty(n_tail, np.int32), np.empty(n_tail, np.int32),
                np.empty(n_tail, np.float32))
    except MemoryError:
        return _no_room("split_classes_native")
    rc = dll.pml_split_classes_fill(
        _ptr(cols), _ptr(vals), n, k, _ptr(code), int(n_hot), block_rows,
        workers, _ptr(tail_start), _ptr(x_hot),
        None if cols_planned is None else _ptr(cols_planned),
        _ptr(vals_planned), *map(_ptr, tail))
    if rc != 0:
        raise ValueError("split_classes_native: hot rank out of range "
                         "(or the batch changed between the passes)")
    return x_hot, cols_planned, vals_planned, tail, workers
