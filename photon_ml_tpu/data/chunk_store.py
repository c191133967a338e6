"""Disk-backed chunk store: the third tier under ``data.chunked_batch``.

Reference counterpart: Spark's disk-spillable RDD persistence — a
partition that does not fit the executor heap spills to local disk and
is re-read (or recomputed from lineage) on the next pass, so the
trainable size is bounded by cluster DISK, not RAM (SURVEY §1 L1).
Round 5 removed the HBM residency cap by streaming compiled chunk
batches through the device, but every chunk still lived in host RAM
(26.4 GB RSS at 3×10⁷ examples) — the KDD2012 shape (1.5×10⁸) had no
single-host path.  Snap ML reaches datasets 10× beyond RAM with the
same three-tier pattern this module completes: NVMe/disk → host
staging window → accelerator, with prefetch overlapping every tier
(PAPERS.md).

Design:

- **One atomic ``.npz`` per chunk** under ``<spill_dir>/chunks/``,
  written with the plan cache's tmp+``os.replace`` primitive
  (``cache.plan_cache.atomic_savez``) and keyed by a blake2b content
  fingerprint of the exact build inputs × the build configuration ×
  a format version — so a spilled dataset doubles as a persistent
  warm-ETL artifact: the next run with the same data skips the chunk
  compile entirely.  Offsets are NOT part of the payload (they change
  every GAME coordinate-descent iteration); ``ChunkedBatch`` overlays
  the current offsets window at access time, so spilled files stay
  valid across CD sweeps and across runs.
- **Memory-mapped loads**: ``np.savez`` members are STORED (never
  deflated), i.e. each member is a whole ``.npy`` at a knowable file
  offset — ``_open_npz_mmap`` parses the zip local headers and hands
  back ``np.memmap`` views, so a loaded chunk costs address space and
  page-cache traffic, not anonymous RSS, and the OS can reclaim clean
  pages under pressure.  Any parse surprise falls back to a plain
  ``np.load`` copy; any read failure falls back to a rebuild — the
  store must never be able to make a run fail (plan-cache rule).
- **LRU host window**: at most ``host_max_resident`` decoded chunks
  stay live; admission evicts the least-recently-used first, and
  eviction is a reference drop (numpy/memmap frees follow refcounts,
  so an in-flight ``device_put`` holding a reference is always safe).
- **Reader accounting**: the streaming prefetch thread registers as a
  reader (``begin_read``/``end_read``);
  ``ChunkedGLMObjective.invalidate`` asserts the store is quiesced
  (``assert_quiesced``) before dropping buffers, so a use-after-evict
  race is a loud error, not a corruption.
"""

from __future__ import annotations

import errno
import hashlib
import json
import logging
import os
import shutil
import struct
import threading
import zipfile
from collections import OrderedDict

import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.reliability import faults as _faults
from photon_ml_tpu.reliability import retry as _retry

logger = logging.getLogger(__name__)

# On-disk chunk format version: bump when the member layout changes —
# the version rides in the file NAME, so stale entries are clean misses.
CHUNK_FORMAT_VERSION = 1

# Per-piece array leaves spilled verbatim.  ``offsets`` is deliberately
# absent: it is CD-iteration state, overlaid by ``ChunkedBatch.chunk``.
_LEAF_FIELDS = ("values", "col_ids", "labels", "weights", "mask")


def release_free_heap() -> None:
    """Return freed allocator arenas to the OS (glibc ``malloc_trim``).

    The one-chunk-at-a-time spill build allocates and frees each
    chunk's arrays plus the zip writer's buffers in turn; glibc retains
    much of that as arena slack, which then reads as permanent RSS —
    the exact number an out-of-core build exists to bound.  Best-effort
    and Linux-only; a no-op anywhere else."""
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except Exception:  # photon-lint: disable=swallowed-exception (non-glibc platforms: nothing to trim)
        pass


def resolve_spill_dir(spill_dir: str | None) -> str | None:
    """Explicit argument, else ``$PHOTON_ML_TPU_SPILL_DIR``, else None
    (chunks stay host-resident, the pre-round-8 behavior)."""
    if spill_dir is not None:
        return spill_dir
    from photon_ml_tpu.config import read_env

    return read_env("PHOTON_ML_TPU_SPILL_DIR") or None


class ChunkStoreSpillError(RuntimeError):
    """A spill write failed for CAPACITY, not transience: one
    actionable error naming the spill dir, the bytes the chunk needed,
    and the bytes the filesystem had free (ISSUE 9 satellite — the raw
    ``OSError(ENOSPC)`` used to surface from the prefetch thread with
    no context at all)."""

    def __init__(self, spill_dir: str, bytes_needed: int,
                 bytes_free: int | None):
        self.spill_dir = spill_dir
        self.bytes_needed = int(bytes_needed)
        self.bytes_free = bytes_free
        free = ("unknown" if bytes_free is None
                else f"{bytes_free / 1e6:.1f} MB")
        super().__init__(
            f"chunk spill to {spill_dir!r} out of space: chunk needs "
            f"~{bytes_needed / 1e6:.1f} MB, {free} free — free disk "
            "space, point spill_dir/$PHOTON_ML_TPU_SPILL_DIR at a "
            "larger volume, or raise chunk granularity "
            "(chunk_rows / re_chunk_entities) to shrink per-chunk "
            "spill size")


def _free_bytes(path: str) -> int | None:
    """Free bytes on the filesystem holding ``path`` (nearest existing
    ancestor), or None when even that cannot be determined."""
    p = os.path.abspath(path)
    while p and not os.path.exists(p):
        parent = os.path.dirname(p)
        if parent == p:
            break
        p = parent
    try:
        return shutil.disk_usage(p).free
    except OSError:  # photon-lint: disable=swallowed-exception (free-space probe is advisory; the spill error carries 'unknown')
        return None


# Spill dirs already warned about (degrade-to-resident is announced
# ONCE per dir per process, not once per chunk build).
_DEGRADED_DIRS: set[str] = set()
_DEGRADED_LOCK = threading.Lock()


def probe_spill_dir(spill_dir: str | None) -> str | None:
    """``spill_dir`` if it is writable, else None — the documented
    degradation for an unwritable spill dir: the caller falls back to
    the resident (pre-round-8) path with ONE warning instead of dying
    chunks deep into a build.  Streamed random effects, where the
    store is the architecture rather than an optimization, must NOT
    degrade — they keep calling the store directly and surface the
    error."""
    if spill_dir is None:
        return None
    # Unique probe name: spill dirs are SHARED across runs by design
    # (content-addressed warm reuse), so a fixed name would let two
    # concurrent probes race on the remove and spuriously degrade a
    # healthy dir (review finding).
    probe = os.path.join(spill_dir, "chunks",
                         f".probe-{os.getpid()}-{threading.get_ident()}")
    try:
        os.makedirs(os.path.dirname(probe), exist_ok=True)
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
        return spill_dir
    except OSError as e:
        with _DEGRADED_LOCK:
            first = spill_dir not in _DEGRADED_DIRS
            _DEGRADED_DIRS.add(spill_dir)
        if first:
            logger.warning(
                "spill dir %r is not writable (%r); DEGRADING to the "
                "host-resident path — host RSS is no longer bounded by "
                "the chunk window for this build", spill_dir, e)
            telemetry.count("reliability.degraded")
        return None


def store_key(rows, labels: np.ndarray, weights: np.ndarray, dim: int,
              chunk_rows: int, layout: str, n_dev: int,
              row_capacity: int, drop_ell_with_grr: bool = True) -> str:
    """Content fingerprint of everything that shapes the spilled chunk
    payloads (the plan cache's keying discipline: exact inputs × build
    config × format version; offsets excluded — they are external).

    GRR-layout chunks embed COMPILED plans, so the planner/builder
    semantics version is part of their key — a ``PLANNER_VERSION``
    bump orphans old GRR chunk files exactly as it orphans plan-cache
    entries, instead of warm-serving stale plans to new kernel code.
    ``drop_ell_with_grr`` changes the spilled ELL arrays and keys too.
    """
    from photon_ml_tpu.cache.plan_cache import dataset_fingerprint

    cfg_dict = {"chunk_rows": int(chunk_rows), "layout": layout,
                "n_dev": int(n_dev), "k": int(row_capacity)}
    if layout == "grr":
        from photon_ml_tpu.data.grr import PLANNER_VERSION

        cfg_dict["planner"] = PLANNER_VERSION
        cfg_dict["drop_ell"] = bool(drop_ell_with_grr)
    fp = dataset_fingerprint(
        np.asarray(rows.indptr), np.asarray(rows.vals, np.float32), dim,
        extra=(np.asarray(rows.cols), np.asarray(labels, np.float32),
               np.asarray(weights, np.float32)))
    cfg = hashlib.blake2b(
        json.dumps(cfg_dict, sort_keys=True).encode(),
        digest_size=6).hexdigest()
    return f"{fp}-{cfg}"


# ---------------------------------------------------------------------------
# Encode / decode (the plan cache's tree-path-key scheme, one level up:
# a chunk is 1..n_dev SparseBatch pieces, each optionally carrying a
# compiled GRR plan serialized by the plan cache's own node codec).
# ---------------------------------------------------------------------------


def encode_chunk(chunk) -> tuple[dict, dict]:
    """Chunk (SparseBatch | list of per-device SparseBatch) → (manifest,
    arrays) ready for ``atomic_savez``."""
    from photon_ml_tpu.cache.plan_cache import _encode_node

    pieces = chunk if isinstance(chunk, list) else [chunk]
    arrays: dict = {}
    metas = []
    for j, b in enumerate(pieces):
        pfx = f"p{j}."
        for f in _LEAF_FIELDS:
            arrays[pfx + f] = np.asarray(getattr(b, f))
        metas.append({
            "dim": int(b.dim),
            "grr": _encode_node(b.grr, pfx + "g.", arrays),
        })
    meta = {"version": CHUNK_FORMAT_VERSION,
            "mesh": isinstance(chunk, list), "pieces": metas}
    return meta, arrays


def encode_array_chunk(chunk: dict) -> tuple[dict, dict]:
    """Generic flat array-dict chunk → (manifest, arrays): the scoring
    pipeline's chunk payloads (ISSUE 4) are plain name → ndarray maps,
    not SparseBatch pieces — same spill/mmap/LRU machinery, simpler
    codec."""
    arrays = {k: np.asarray(v) for k, v in chunk.items()}
    meta = {"version": CHUNK_FORMAT_VERSION, "kind": "arrays",
            "keys": sorted(arrays)}
    return meta, arrays


def decode_array_chunk(meta: dict, arrays) -> dict:
    """Inverse of ``encode_array_chunk``; memmap views pass through
    (score chunks stay file-backed in the host window)."""
    if meta.get("version") != CHUNK_FORMAT_VERSION:
        raise ValueError(f"chunk format {meta.get('version')!r} != "
                         f"{CHUNK_FORMAT_VERSION}")
    if meta.get("kind") != "arrays":
        raise ValueError(f"chunk kind {meta.get('kind')!r} != 'arrays'")
    return {k: arrays[k] for k in meta["keys"]}


# Entity-block chunk leaves (streamed random effects, ISSUE 5): a chunk
# is ``re_chunk_entities`` padded entity problems of one size bucket —
# x [C, cap, p] plus [C, cap] scalar planes.  Offsets are (as ever)
# absent: they are CD-iteration state, scattered in at load time from
# the coordinate's resident per-example maps.
_ENTITY_LEAF_FIELDS = ("x", "labels", "weights", "mask")


def encode_entity_chunk(chunk: dict) -> tuple[dict, dict]:
    """Entity-block chunk (name → ndarray with the ``x``/``labels``/
    ``weights``/``mask`` leaves) → (manifest, arrays).  The random-
    effect streaming codec: same spill/mmap/LRU machinery as the
    training/scoring codecs, keyed leaves so a decode can never bind a
    plane to the wrong role."""
    arrays = {f: np.asarray(chunk[f]) for f in _ENTITY_LEAF_FIELDS}
    meta = {"version": CHUNK_FORMAT_VERSION, "kind": "entity_blocks"}
    return meta, arrays


def decode_entity_chunk(meta: dict, arrays) -> dict:
    """Inverse of ``encode_entity_chunk``; memmap views pass through
    (entity blocks stay file-backed in the host window)."""
    if meta.get("version") != CHUNK_FORMAT_VERSION:
        raise ValueError(f"chunk format {meta.get('version')!r} != "
                         f"{CHUNK_FORMAT_VERSION}")
    if meta.get("kind") != "entity_blocks":
        raise ValueError(
            f"chunk kind {meta.get('kind')!r} != 'entity_blocks'")
    return {f: arrays[f] for f in _ENTITY_LEAF_FIELDS}


ENTITY_CHUNK_CODEC = (encode_entity_chunk, decode_entity_chunk)


# Fused-cycle sidecar chunks (ISSUE 11): the cycle-aligned layout
# co-locates, per EXAMPLE chunk, every random effect's per-row entity
# index + (projected) feature planes next to the fixed-effect chunk the
# same rows live in — so ONE prefetched chunk pair feeds every
# coordinate of a fused CD cycle.  Payloads are flat name → ndarray
# maps ("<coordinate>.x" [R, p], "<coordinate>.idx" [R]); the kind tag
# keeps a fused sidecar from ever decoding as a scoring chunk.


def encode_fused_chunk(chunk: dict) -> tuple[dict, dict]:
    """Fused-training sidecar chunk → (manifest, arrays)."""
    arrays = {k: np.asarray(v) for k, v in chunk.items()}
    meta = {"version": CHUNK_FORMAT_VERSION, "kind": "fused_rows",
            "keys": sorted(arrays)}
    return meta, arrays


def decode_fused_chunk(meta: dict, arrays) -> dict:
    """Inverse of ``encode_fused_chunk``; memmap views pass through."""
    if meta.get("version") != CHUNK_FORMAT_VERSION:
        raise ValueError(f"chunk format {meta.get('version')!r} != "
                         f"{CHUNK_FORMAT_VERSION}")
    if meta.get("kind") != "fused_rows":
        raise ValueError(f"chunk kind {meta.get('kind')!r} != "
                         "'fused_rows'")
    return {k: arrays[k] for k in meta["keys"]}


FUSED_CHUNK_CODEC = (encode_fused_chunk, decode_fused_chunk)


def array_content_key(arrays, cfg: dict) -> str:
    """Content fingerprint for chunk payloads derived from plain host
    arrays (the streamed-RE analog of ``store_key``): exact input
    bytes × build configuration; the format version rides in the file
    name as everywhere else.  ``arrays`` is an iterable of ndarrays
    hashed with dtype/shape framing so transposed or reshaped inputs
    cannot collide."""
    h = hashlib.blake2b(digest_size=10)
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint8).reshape(-1))
    cfg_h = hashlib.blake2b(
        json.dumps(cfg, sort_keys=True).encode(),
        digest_size=6).hexdigest()
    return f"{h.hexdigest()}-{cfg_h}"


def decode_chunk(meta: dict, arrays):
    """Inverse of ``encode_chunk``; ``arrays`` may be lazy (memmap
    views or an open NpzFile).  Offsets come back ZERO — the caller
    (``ChunkedBatch.chunk``) overlays the current window."""
    from photon_ml_tpu.cache.plan_cache import _decode_node
    from photon_ml_tpu.data.batch import SparseBatch

    if meta.get("version") != CHUNK_FORMAT_VERSION:
        raise ValueError(f"chunk format {meta.get('version')!r} != "
                         f"{CHUNK_FORMAT_VERSION}")
    pieces = []
    for j, pm in enumerate(meta["pieces"]):
        pfx = f"p{j}."
        labels = np.asarray(arrays[pfx + "labels"])
        pieces.append(SparseBatch(
            values=arrays[pfx + "values"],
            col_ids=arrays[pfx + "col_ids"],
            labels=labels,
            weights=arrays[pfx + "weights"],
            offsets=np.zeros(labels.shape[0], np.float32),
            mask=arrays[pfx + "mask"],
            dim=int(pm["dim"]),
            grr=_decode_node(pm["grr"], pfx + "g.", arrays),
        ))
    return pieces if meta["mesh"] else pieces[0]


# Parsed member index per (path, mtime_ns, size): a streaming sweep
# re-opens the same files every pass (window misses), and the zip +
# npy header walk is pure re-derivation — the payload offsets cannot
# change without the stat signature changing.
_NPZ_INDEX: dict = {}
_NPZ_INDEX_LOCK = threading.Lock()
_NPZ_INDEX_MAX = 4096


def _npz_index(path: str) -> tuple:
    """[(member name, dtype, shape, payload offset)] for an
    uncompressed ``.npz``, cached by stat signature."""
    st = os.stat(path)
    sig = (path, st.st_mtime_ns, st.st_size)
    with _NPZ_INDEX_LOCK:
        idx = _NPZ_INDEX.get(sig)
    if idx is not None:
        return idx
    members = []
    with open(path, "rb") as fh, zipfile.ZipFile(fh) as zf:
        for info in zf.infolist():
            if info.compress_type != zipfile.ZIP_STORED:
                raise ValueError(f"compressed member {info.filename!r}")
            fh.seek(info.header_offset)
            hdr = fh.read(30)
            if len(hdr) != 30 or hdr[:4] != b"PK\x03\x04":
                raise ValueError("bad zip local header")
            name_len, extra_len = struct.unpack("<HH", hdr[26:30])
            fh.seek(info.header_offset + 30 + name_len + extra_len)
            version = np.lib.format.read_magic(fh)
            if version == (1, 0):
                shape, fortran, dtype = \
                    np.lib.format.read_array_header_1_0(fh)
            elif version == (2, 0):
                shape, fortran, dtype = \
                    np.lib.format.read_array_header_2_0(fh)
            else:
                raise ValueError(f"npy format {version}")
            if fortran or dtype.hasobject:
                raise ValueError("unsupported npy layout")
            name = info.filename
            if name.endswith(".npy"):
                name = name[:-4]
            members.append((name, dtype, shape, fh.tell()))
    idx = tuple(members)
    with _NPZ_INDEX_LOCK:
        if len(_NPZ_INDEX) >= _NPZ_INDEX_MAX:
            _NPZ_INDEX.clear()
        _NPZ_INDEX[sig] = idx
    return idx


def _open_npz_mmap(path: str) -> dict:
    """Memory-mapped views of every member of an uncompressed ``.npz``.

    ``np.savez`` members are ZIP_STORED whole ``.npy`` files, so each
    array's data sits at (local-header offset + header) — parse the
    30-byte local header for the name/extra lengths (the central
    directory's copies can differ), then the npy header, and
    ``np.memmap`` the payload.  Raises on anything unexpected; the
    caller falls back to a plain copying load."""
    return {name: np.memmap(path, mode="r", dtype=dtype, shape=shape,
                            offset=offset)
            for name, dtype, shape, offset in _npz_index(path)}


class SharedChunkWindow:
    """One LRU residency budget shared by SEVERAL chunk stores.

    The legacy (per-coordinate) CD cycle streams the fixed-effect store
    and each random effect's entity store in turn; with per-store
    windows each coordinate pins its own ``host_max_resident`` chunks
    for the whole descent, so the cycle's true host footprint is
    (window × streamed coordinates) and the coordinates thrash each
    other's budget expectations (ISSUE 11 satellite).  Registering the
    stores in one group makes ``budget`` the TOTAL decoded-chunk bound
    across all of them: admission evicts the globally least-recently-
    used chunk, whichever store owns it — the active coordinate's sweep
    naturally fills the window, and the previous coordinate's stale
    chunks are the first to go.

    Lock order: the group lock is always taken FIRST, store locks
    second (``admit``/``touch`` are called by stores OUTSIDE their own
    lock); eviction is a reference drop, so a reader holding a chunk
    reference is never invalidated.
    """

    def __init__(self, budget: int):
        self.budget = max(1, int(budget))
        self._lock = threading.RLock()
        # (id(store), chunk index) -> store, in LRU order.
        self._order: OrderedDict = OrderedDict()
        self.evictions = 0

    @property
    def n_resident(self) -> int:
        with self._lock:
            return len(self._order)

    def admit(self, store: "ChunkStore", i: int) -> None:
        with self._lock:
            key = (id(store), i)
            if key in self._order:
                self._order.move_to_end(key)
                return
            while len(self._order) >= self.budget:
                (_, j), victim = self._order.popitem(last=False)
                victim._drop(j)
                self.evictions += 1
            self._order[key] = store

    def touch(self, store: "ChunkStore", i: int) -> None:
        with self._lock:
            key = (id(store), i)
            if key in self._order:
                self._order.move_to_end(key)

    def drop_store(self, store: "ChunkStore") -> None:
        """Forget every entry owned by ``store`` (its window was
        cleared directly, e.g. ``drop_resident``)."""
        with self._lock:
            for key in [k for k, s in self._order.items() if s is store]:
                del self._order[key]


class ChunkStore:
    """Spilled chunks on disk + an LRU window of decoded host chunks.

    ``rebuild(i) -> chunk`` is the lineage fallback: a missing or
    unreadable chunk file is re-derived from the original rows (and
    re-spilled), so disk loss degrades to recompute, never to failure.

    Thread contract: ``get`` is safe from the prefetch thread and the
    main thread; mutation of the window happens under one lock.  The
    instrumentation fields (``loads``/``hits``/``rebuilds``/
    ``peak_resident``/``access_log``) back the LRU-bound and
    determinism tests.
    """

    def __init__(self, spill_dir: str, key: str, n_chunks: int,
                 host_max_resident: int = 2, rebuild=None, codec=None,
                 window_group: "SharedChunkWindow | None" = None):
        self.dir = os.path.join(spill_dir, "chunks")
        self.key = key
        self.n_chunks = n_chunks
        self.host_max_resident = max(1, int(host_max_resident))
        self._rebuild = rebuild
        # Shared residency budget across stores (ISSUE 11 satellite):
        # when set, the GROUP owns eviction — this store's window is
        # bounded by the group's total budget, not its own count.
        self._window_group = window_group
        # (encode, decode) pair; default is the SparseBatch chunk codec
        # (training), ``(encode_array_chunk, decode_array_chunk)`` for
        # the scoring pipeline's flat array-dict chunks.
        self._encode, self._decode = codec or (encode_chunk, decode_chunk)
        self._resident: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self._readers = 0
        self.loads = 0        # disk loads (misses)
        self.hits = 0         # window hits
        self.rebuilds = 0     # corrupt/missing fallbacks taken
        self.spills = 0       # chunk files written
        self.peak_resident = 0
        self.access_log: list[int] = []   # miss+hit order (determinism)

    # -- paths -------------------------------------------------------------

    def path(self, i: int) -> str:
        return os.path.join(
            self.dir, f"{self.key}-c{i:05d}-v{CHUNK_FORMAT_VERSION}.npz")

    def has(self, i: int) -> bool:
        return os.path.exists(self.path(i))

    # -- window ------------------------------------------------------------

    @property
    def n_resident(self) -> int:
        with self._lock:
            return len(self._resident)

    @property
    def resident_nbytes(self) -> int:
        """Anonymous-host bytes the window pins (memmap leaves count
        zero — their pages are file-backed and reclaimable)."""
        total = 0
        with self._lock:
            chunks = list(self._resident.values())
        for ch in chunks:
            if isinstance(ch, dict):            # array-dict chunks
                leaves = list(ch.values())
            else:
                leaves = [getattr(b, f)
                          for b in (ch if isinstance(ch, list) else [ch])
                          for f in _LEAF_FIELDS]
            for a in leaves:
                if not isinstance(a, np.memmap):
                    total += np.asarray(a).nbytes
        return total

    def _admit(self, i: int, chunk) -> None:
        if self._window_group is not None:
            # Group-governed residency: install locally, then let the
            # group evict the global LRU (possibly from another store).
            # The group call happens OUTSIDE this store's lock — lock
            # order is group first, store second, everywhere.
            with self._lock:
                self._resident[i] = chunk
                self._resident.move_to_end(i)
                self.peak_resident = max(self.peak_resident,
                                         len(self._resident))
            self._window_group.admit(self, i)
            return
        with self._lock:
            if i in self._resident:
                self._resident.move_to_end(i)
                return
            while len(self._resident) >= self.host_max_resident:
                self._resident.popitem(last=False)   # LRU; refs freed
            self._resident[i] = chunk
            self.peak_resident = max(self.peak_resident,
                                     len(self._resident))

    def _drop(self, i: int) -> None:
        """Group-eviction callback: forget chunk ``i`` (ref drop)."""
        with self._lock:
            self._resident.pop(i, None)

    def join_window_group(self, group: "SharedChunkWindow | None") -> None:
        """Install (or clear) a shared residency group on a live store.

        Chunks already resident are registered with the group in their
        current LRU order (possibly evicting under the group's budget),
        so a store built before the group existed — the fixed-effect
        chunked batch comes out of dataset prep, streamed-RE stores out
        of the coordinate builders — joins with consistent accounting.
        """
        old = self._window_group
        if old is not None and old is not group:
            old.drop_store(self)
        self._window_group = group
        if group is None:
            return
        with self._lock:
            resident = list(self._resident)
        for i in resident:
            group.admit(self, i)

    def drop_resident(self) -> None:
        """Free the whole window (requires quiescence — see
        ``assert_quiesced``)."""
        self.assert_quiesced()
        with self._lock:
            self._resident.clear()
        if self._window_group is not None:
            self._window_group.drop_store(self)

    # -- reader accounting (prefetch quiescence) ---------------------------

    def begin_read(self) -> None:
        with self._lock:
            self._readers += 1

    def end_read(self) -> None:
        with self._lock:
            self._readers -= 1

    def assert_quiesced(self) -> None:
        """Raise if a prefetch reader is still active — freeing or
        invalidating chunks under a live reader is the use-after-evict
        race this store exists to prevent."""
        with self._lock:
            if self._readers:
                raise RuntimeError(
                    f"chunk store has {self._readers} active prefetch "
                    "reader(s); quiesce the pipeline before freeing "
                    "chunks")

    # -- spill / load ------------------------------------------------------

    def put(self, i: int, chunk, keep_resident: bool | None = None) -> None:
        """Spill chunk ``i`` (atomic write) and optionally admit it to
        the window.  Default admission: only the first
        ``host_max_resident`` chunks — the ones the deterministic sweep
        order will want first."""
        from photon_ml_tpu.cache.plan_cache import atomic_savez

        meta, arrays = self._encode(chunk)
        path = self.path(i)

        def _write():
            # The fault seam sits INSIDE the attempt so a transient
            # injected write error exercises the same retry the real
            # failure would.
            _faults.fire("store.spill", path=path, chunk=i)
            atomic_savez(path, meta, arrays)

        try:
            _retry.run_with_retries(_write, f"chunk spill {path}")
        except OSError as e:
            if e.errno == errno.ENOSPC:
                # Capacity, not transience: ONE actionable error with
                # the numbers the operator needs (satellite — the raw
                # ENOSPC used to propagate from the prefetch thread).
                telemetry.count("reliability.actionable_errors")
                raise ChunkStoreSpillError(
                    os.path.dirname(self.dir) or self.dir,
                    sum(int(np.asarray(a).nbytes)
                        for a in arrays.values()),
                    _free_bytes(self.dir)) from e
            raise
        with self._lock:
            # ``put`` runs on the build thread AND (rebuild re-spill)
            # the prefetch thread — the counter is shared state.
            self.spills += 1
        telemetry.count("store.spills")
        try:
            telemetry.count("store.bytes_spilled",
                            os.path.getsize(self.path(i)))
        except OSError:  # photon-lint: disable=swallowed-exception (racing cleanup; the size metric is best-effort)
            pass
        if keep_resident is None:
            keep_resident = i < self.host_max_resident
        if keep_resident:
            self._admit(i, chunk)

    def get(self, i: int):
        """Chunk ``i`` as host pieces: window hit, else disk load
        (memory-mapped), else rebuild-from-lineage + re-spill."""
        with self._lock:
            if i in self._resident:
                self._resident.move_to_end(i)
                self.hits += 1
                self.access_log.append(i)
                hit = self._resident[i]
                telemetry.count("store.hits")
            else:
                hit = None
        if hit is not None:
            if self._window_group is not None:
                self._window_group.touch(self, i)
            return hit
        chunk = self._load(i)
        self._admit(i, chunk)
        return chunk

    def _load(self, i: int):
        path = self.path(i)
        with self._lock:
            self.access_log.append(i)
            self.loads += 1
        telemetry.count("store.loads")

        def _attempt():
            # Fault seam per ATTEMPT (a transient injected read error
            # exercises the same bounded retry a flaky disk would).
            _faults.fire("store.load", path=path, chunk=i)
            try:
                arrays = _open_npz_mmap(path)
                telemetry.count("store.mmap_loads")
            except (zipfile.BadZipFile, ValueError, OSError):
                # mmap parse surprise: fall back to a copying load
                # before declaring the file dead.
                arrays = dict(np.load(path, allow_pickle=False))
                telemetry.count("store.copy_loads")
            try:
                telemetry.count("store.bytes_read",
                                os.path.getsize(path))
            except OSError:  # photon-lint: disable=swallowed-exception (best-effort size metric; racing cleanup)
                pass
            meta = json.loads(bytes(np.asarray(arrays["__meta__"]))
                              .decode())
            return self._decode(meta, arrays)

        try:
            # Transient read errors (EIO and friends) retry with
            # bounded backoff before the lineage rebuild; corruption
            # (ValueError / BadZipFile) and ENOENT go straight to
            # rebuild — retrying cannot change file content.
            return _retry.run_with_retries(
                _attempt, f"chunk load {path}")
        except Exception as e:
            if self._rebuild is None:
                raise
            logger.warning(
                "chunk store: unreadable chunk %s (%r); rebuilding",
                path, e)
            with self._lock:
                self.rebuilds += 1
            telemetry.count("store.rebuilds")
            chunk = self._rebuild(i)
            try:
                self.put(i, chunk, keep_resident=False)
            except Exception as we:   # re-spill is best-effort
                logger.warning("chunk store: re-spill of chunk %d "
                               "failed (%r)", i, we)
            return chunk
