"""Chunked batches: the beyond-HBM-residency training class.

Reference counterpart: Spark never holds a dataset on one machine — it
streams HDFS splits through executors and recomputes from lineage, so
the trainable size is bounded by the CLUSTER, not one host (SURVEY.md
§1 L1, §5.8 [expected structure, mount unavailable]).  The resident
TPU path inverts that trade: a compiled GRR plan must live in HBM for
the whole fit (~1.6 GB per 10⁶ examples measured, PERF.md), capping a
16 GB v5e chip at ~9×10⁶ examples.

This module removes the cap the same way Spark does — by streaming —
while keeping every FLOP on the TPU: the dataset is compiled ONCE into
K congruent chunk batches (identical pytree structure and leaf shapes,
the same trick the mesh-sharded build uses for multi-device
congruence), and every objective evaluation streams chunks through HBM,
accumulating (loss, gradient, HVP, Hessian-diagonal) partials on
device.  Every data-side quantity the GLM objective computes is a
linear reduction over examples, so chunked accumulation is EXACT up to
float-summation reordering (tested against the resident path).

Because the chunks are congruent, the per-chunk device program compiles
once and replays K times per pass; ``optim.streaming`` double-buffers
the host→device transfer of chunk i+1 under chunk i's compute, and
keeps up to ``max_resident`` chunks live in HBM so datasets that DO fit
pay the transfer once (the resident and streaming regimes are one code
path).

Three residency tiers (round 8 completes the set):

1. **HBM** — ``max_resident`` device chunks (``optim.streaming``).
2. **Host RAM** — without ``spill_dir``, every chunk lives as numpy
   leaves in ``chunks`` (bounded by host RAM: 26.4 GB at 3×10⁷
   examples, the round-5 wall).
3. **Disk** — with ``spill_dir`` (``$PHOTON_ML_TPU_SPILL_DIR`` is
   honored by the config/estimator layer, not here),
   chunks spill to atomic per-chunk ``.npz`` files
   (``data.chunk_store``) and at most ``host_max_resident`` decoded
   chunks stay live (memory-mapped, LRU) — host RSS is bounded by the
   WINDOW, dataset size by disk, and ``optim.streaming``'s prefetch
   thread overlaps disk read → host staging → async device_put of
   chunks i+1..i+depth under chunk i's compute.  Offsets (GAME CD
   residual state) stay OUT of the spilled payload — ``chunk(i)``
   overlays the live window — so ``set_offsets`` is an O(n) host write
   and spilled files double as persistent warm-ETL artifacts across
   runs.

Layouts per chunk (``layout=``):
- ``"grr"`` — compiled GRR plans (``data.grr.build_sharded_grr_pairs``,
  chunks-as-shards): kernel-speed steps; ~1.6 GB/10⁶ examples streamed
  per pass — right when host↔device bandwidth is PCIe-class.
- ``"ell"`` — plain ELL (8 bytes/nnz): XLA gather/scatter steps, ~20×
  smaller stream; right when transfer dominates (or when even the ELL
  no longer fits and streaming volume is the binding cost).

With ``mesh=``, chunks × shards compose: each chunk is built as
congruent PER-DEVICE sub-batches (one more level of the same
congruence) and assembled onto the mesh per use; gradient partials
then meet in the distributed objective's existing psum.
"""

from __future__ import annotations

import dataclasses
import logging

import numpy as np

from photon_ml_tpu.data.batch import SparseBatch

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ChunkedBatch:
    """K congruent chunk batches over one example axis.

    Resident mode (``store`` is None): ``chunks[i]`` is a
    ``SparseBatch`` with HOST (numpy) leaves — or, when ``mesh`` is
    set, a list of per-device host sub-batches to be assembled
    example-sharded on use.  Spilled mode (``store`` set): ``chunks``
    holds placeholders and ``chunk(i)`` pulls from the disk-backed LRU
    window, overlaying the current ``offsets_host`` slice.  All chunks
    have identical pytree structure and leaf shapes (one compile
    serves all) either way; consumers go through ``chunk(i)``.
    """

    chunks: list
    dim: int
    n: int                 # real examples (before padding)
    chunk_rows: int        # examples per chunk (last chunk padded)
    layout: str
    mesh: object | None = None   # jax.sharding.Mesh | None
    store: object | None = None  # data.chunk_store.ChunkStore | None
    # Spilled mode: offsets over the FULL padded chunk grid
    # [n_chunks·chunk_rows] — CD-iteration state kept out of the
    # spilled payload so chunk files survive ``set_offsets``.
    offsets_host: np.ndarray | None = None
    # Fleet mode (parallel.fleet): the contiguous chunk shard THIS host
    # owns, and its sentinel-padded chunk-synchronized schedule (same
    # length on every host).  None = single-host run, every chunk.
    local_chunk_ids: list | None = None
    schedule: list | None = None

    @property
    def n_chunks(self) -> int:
        return len(self.chunks)

    @property
    def owned_chunk_ids(self) -> list:
        """Chunk ids this host streams (all of them outside a fleet)."""
        if self.local_chunk_ids is None:
            return list(range(self.n_chunks))
        return list(self.local_chunk_ids)

    @property
    def chunk_schedule(self) -> list:
        """The per-host chunk visit order: owned chunks first, then
        ``fleet.EMPTY_CHUNK`` sentinels padding ragged shards to the
        fleet-common step count (single-host: just every chunk)."""
        if self.schedule is None:
            return list(range(self.n_chunks))
        return list(self.schedule)

    def chunk_slice(self, i: int) -> tuple[int, int]:
        """Real-example range [lo, hi) covered by chunk i."""
        lo = i * self.chunk_rows
        return lo, min(lo + self.chunk_rows, self.n)

    def chunk(self, i: int):
        """Host pieces of chunk i, current offsets installed — the one
        accessor every consumer uses (resident list or spill store)."""
        if self.store is None:
            return self.chunks[i]
        c = self.store.get(i)
        off = self.offsets_host[i * self.chunk_rows:
                                (i + 1) * self.chunk_rows]
        if self.mesh is None:
            return c.replace(offsets=off)
        per = self.chunk_rows // len(c)
        return [b.replace(offsets=off[j * per:(j + 1) * per])
                for j, b in enumerate(c)]

    def set_offsets(self, offsets: np.ndarray) -> None:
        """Install new per-example offsets (GAME coordinate-descent
        residual passing), zero-padded to the chunk grid.  Resident
        mode rewrites the host chunks; spilled mode only rewrites the
        external offsets window (chunk files are offset-free).  Callers
        holding device copies must invalidate them
        (``optim.streaming.ChunkedGLMObjective.invalidate``)."""
        offsets = np.asarray(offsets, np.float32)
        if offsets.shape[0] != self.n:
            raise ValueError(
                f"offsets length {offsets.shape[0]} != n {self.n}")
        if self.store is not None:
            self.offsets_host = np.zeros(
                self.n_chunks * self.chunk_rows, np.float32)
            self.offsets_host[: self.n] = offsets
            return
        for i in range(self.n_chunks):
            lo, hi = self.chunk_slice(i)
            pad = np.zeros(self.chunk_rows, np.float32)
            pad[: hi - lo] = offsets[lo:hi]
            if self.mesh is None:
                self.chunks[i] = self.chunks[i].replace(offsets=pad)
            else:
                per = self.chunk_rows // len(self.chunks[i])
                self.chunks[i] = [
                    b.replace(offsets=pad[j * per:(j + 1) * per])
                    for j, b in enumerate(self.chunks[i])
                ]


def _host_chunk(cols, vals, labels, weights, offsets, mask, dim,
                grr=None) -> SparseBatch:
    """A SparseBatch with host numpy leaves (no device placement)."""
    return SparseBatch(
        values=np.asarray(vals, np.float32),
        col_ids=np.asarray(cols, np.int32),
        labels=np.asarray(labels, np.float32),
        weights=np.asarray(weights, np.float32),
        offsets=np.asarray(offsets, np.float32),
        mask=np.asarray(mask, np.float32),
        dim=dim,
        grr=grr,
    )


def build_chunked_batch(
    rows,
    dim: int,
    labels: np.ndarray,
    weights: np.ndarray | None = None,
    offsets: np.ndarray | None = None,
    chunk_rows: int | None = None,
    n_chunks: int | None = None,
    layout: str = "grr",
    mesh=None,
    row_capacity: int | None = None,
    drop_ell_with_grr: bool = True,
    cache_dir: str | None = None,
    spill_dir: str | None = None,
    host_max_resident: int = 2,
) -> ChunkedBatch:
    """Compile a dataset into K congruent chunk batches.

    ``rows``: ``SparseRows`` (scale path) or list of (col_ids, values)
    pairs.  Exactly one of ``chunk_rows`` / ``n_chunks`` must be given.
    ``layout``: "grr" or "ell" (see module docstring).  With ``mesh``,
    each chunk is split further into one congruent sub-batch per mesh
    device (chunks × shards).

    The GRR chunk plans are built by the SAME congruent-shapes builder
    the mesh path uses (chunks are shards of the example axis either
    way); hot/mid column sets and capacities are global across chunks,
    so one compiled contraction program serves every chunk.
    ``cache_dir`` enables the on-disk plan cache for those chunk plans
    (``photon_ml_tpu.cache``): the scale run's plan compile is paid
    once per dataset, not once per run.

    ``spill_dir`` (None = stay host-resident) activates the disk tier
    (``data.chunk_store``).  Deliberately EXPLICIT at this layer — the
    ``$PHOTON_ML_TPU_SPILL_DIR`` default is applied by the config/
    estimator layer, so library callers building a resident baseline
    (parity tests, a comparison's control side) cannot be silently flipped to
    the spill store by ambient environment.  With the disk tier on:
    chunks spill to atomic content-keyed ``.npz`` files and at most
    ``host_max_resident`` decoded chunks stay live.  ELL chunks are
    built AND spilled one at a time, so peak RSS during ETL is bounded
    by the window too; GRR chunk plans need the global congruent build
    first (shared hot/mid sets, pooled overflow, common padding), so
    their ETL peak is one full plan set — they spill right after and
    steady-state RSS is bounded either way.  A chunk file that already
    exists for the same content key is NOT rebuilt (warm ETL); a
    missing or corrupt file at sweep time rebuilds from ``rows``
    (lineage), so the store can never fail a run.
    """
    from photon_ml_tpu.data.grr import collect_spill_warnings
    from photon_ml_tpu.data.sparse_rows import SparseRows

    if not isinstance(rows, SparseRows):
        rows = SparseRows.from_rows(rows)
    if layout not in ("grr", "ell"):
        raise ValueError(f"unknown chunk layout {layout!r} "
                         "(supported: 'grr', 'ell')")
    n = len(labels)
    if (chunk_rows is None) == (n_chunks is None):
        raise ValueError("give exactly one of chunk_rows / n_chunks")
    n_dev = 1 if mesh is None else mesh.devices.size
    if n_chunks is not None:
        chunk_rows = -(-n // n_chunks)
    # Pieces must be equal-size: round chunk_rows up to the device grid.
    chunk_rows = -(-chunk_rows // n_dev) * n_dev
    n_chunks = -(-n // chunk_rows)
    per = chunk_rows // n_dev

    # Fleet mode: this host builds/spills/streams ONLY its contiguous
    # chunk shard; ids stay global (the full grid is the coordinate
    # system for offsets and checkpoints).
    from photon_ml_tpu.parallel import fleet as _fleet

    fctx = _fleet.active()
    local_ids = schedule = None
    if fctx is not None and fctx.is_fleet:
        local_ids, schedule = _fleet.shard_chunk_ids(
            n_chunks, fctx.host_id, fctx.n_hosts)

    weights = np.ones(n, np.float32) if weights is None else np.asarray(
        weights, np.float32)
    offsets = np.zeros(n, np.float32) if offsets is None else np.asarray(
        offsets, np.float32)
    labels = np.asarray(labels, np.float32)
    k = row_capacity if row_capacity is not None else max(rows.max_nnz, 1)

    def piece_arrays(p):
        lo = p * per
        hi = min(lo + per, n)
        if lo >= n:
            cols_p = np.zeros((per, k), np.int32)
            vals_p = np.zeros((per, k), np.float32)
            aux = [np.zeros(per, np.float32)] * 4
            return cols_p, vals_p, aux
        cols_p, vals_p = rows[lo:hi].to_ell(row_capacity=k, pad_to=per)
        pad1 = lambda a: np.pad(
            np.asarray(a[lo:hi], np.float32), (0, per - (hi - lo)))
        mask = np.zeros(per, np.float32)
        mask[: hi - lo] = 1.0
        return cols_p, vals_p, [pad1(labels), pad1(weights),
                                pad1(offsets), mask]

    def make_pieces(pieces_arr, grr_pairs, zero_offsets=False):
        pieces = []
        for (cols_p, vals_p, (lab, wt, off, mask)), pair in zip(
                pieces_arr, grr_pairs):
            if pair is not None and drop_ell_with_grr:
                # The plan serves every contraction; the ELL copy would
                # only add 8 bytes/nnz to every chunk transfer.
                cols_p = np.zeros((per, 0), np.int32)
                vals_p = np.zeros((per, 0), np.float32)
            if zero_offsets:
                off = np.zeros(per, np.float32)
            pieces.append(_host_chunk(cols_p, vals_p, lab, wt, off,
                                      mask, dim, grr=pair))
        return pieces

    def group(pieces):
        if mesh is None:
            return pieces
        return [pieces[i * n_dev:(i + 1) * n_dev]
                for i in range(len(pieces) // n_dev)]

    def compile_all(zero_offsets=False, chunk_ids=None):
        """Build the given chunks (default: all) → {chunk_id: chunk}.
        A fleet host passes its shard — GRR hot/mid congruence is then
        per-host, which is sound (the plan layout is a per-chunk
        program detail; only the dim-indexed coefficients are global)
        and keeps ETL cost proportional to the shard."""
        ids = list(range(n_chunks)) if chunk_ids is None else list(chunk_ids)
        ps = [p for i in ids for p in range(i * n_dev, (i + 1) * n_dev)]
        pieces_arr = [piece_arrays(p) for p in ps]
        grr_pairs = [None] * len(ps)
        if layout == "grr":
            from photon_ml_tpu.data.grr import build_sharded_grr_pairs

            grr_pairs = build_sharded_grr_pairs(
                [c for c, _, _ in pieces_arr],
                [v for _, v, _ in pieces_arr],
                dim,
                cache_dir=cache_dir,
            )
        return dict(zip(ids, group(make_pieces(pieces_arr, grr_pairs,
                                               zero_offsets))))

    if spill_dir is not None:
        # Per-host spill subdir: fleet hosts never share chunk files
        # (each opens/spills only its shard, and two hosts on one
        # machine must not race the same window accounting).
        spill_dir = _fleet.host_dir(spill_dir, fctx)
        # Unwritable spill dir DEGRADES to the resident build with one
        # warning (ISSUE 9): losing the disk tier costs memory bound,
        # not the run.
        from photon_ml_tpu.data.chunk_store import probe_spill_dir

        spill_dir = probe_spill_dir(spill_dir)

    if spill_dir is None:
        # One aggregation scope around the whole sharded build: every
        # per-shard sub-plan's spill note folds into ONE summary line
        # (a line a sub-plan buries the end of the log an operator reads).
        with collect_spill_warnings():
            built = compile_all(chunk_ids=local_ids)
        chunks = [built.get(i) for i in range(n_chunks)]
        logger.info(
            "chunked batch: n=%d -> %d chunks x %d rows (%s%s)%s", n,
            n_chunks, chunk_rows, layout,
            f", {n_dev}-device mesh" if mesh else "",
            (f", host {fctx.host_id}/{fctx.n_hosts} shard "
             f"{len(built)} chunks") if local_ids is not None else "")
        return ChunkedBatch(chunks=chunks, dim=dim, n=n,
                            chunk_rows=chunk_rows, layout=layout,
                            mesh=mesh, local_chunk_ids=local_ids,
                            schedule=schedule)

    # -- spilled build: disk tier on, host RSS bounded by the window --
    from photon_ml_tpu.data.chunk_store import ChunkStore, store_key

    key = store_key(rows, labels, weights, dim, chunk_rows=chunk_rows,
                    layout=layout, n_dev=n_dev, row_capacity=k,
                    drop_ell_with_grr=drop_ell_with_grr)

    def build_chunk_ell(i):
        """One ELL chunk, independently of the others (congruence is
        by construction: shared k / per / padding grid)."""
        ps = range(i * n_dev, (i + 1) * n_dev)
        pieces = make_pieces([piece_arrays(p) for p in ps],
                             [None] * n_dev, zero_offsets=True)
        return pieces if mesh is not None else pieces[0]

    def rebuild(i):
        """Lineage fallback for a missing/corrupt chunk file."""
        if layout == "ell":
            return build_chunk_ell(i)
        # GRR congruence (shared hot/mid sets, pooled overflow, common
        # padding) is a GLOBAL property of this host's plan set:
        # rebuilding one chunk means rebuilding the set (the plan cache
        # makes this one load when cache_dir is set).  Heal every
        # missing sibling while the set is in hand.
        built = compile_all(zero_offsets=True, chunk_ids=local_ids)
        for j, ch in built.items():
            if j != i and not store.has(j):
                store.put(j, ch, keep_resident=False)
        return built[i]

    store = ChunkStore(spill_dir, key, n_chunks,
                       host_max_resident=host_max_resident,
                       rebuild=rebuild)
    owned = list(range(n_chunks)) if local_ids is None else local_ids
    missing = [i for i in owned if not store.has(i)]
    with collect_spill_warnings():   # one summary per sharded build
        if missing and layout == "ell":
            # Build-time spill: one chunk in flight at a time — ETL
            # peak RSS is (window + 1) chunks, not the dataset.
            for i in missing:
                store.put(i, build_chunk_ell(i))
        elif missing:
            built = compile_all(zero_offsets=True, chunk_ids=local_ids)
            for i in missing:
                store.put(i, built[i])
    if missing:
        from photon_ml_tpu.data.chunk_store import release_free_heap

        release_free_heap()   # build churn must not read as steady RSS
    offsets_host = np.zeros(n_chunks * chunk_rows, np.float32)
    offsets_host[:n] = offsets
    logger.info(
        "chunked batch: n=%d -> %d chunks x %d rows (%s%s), spilled to "
        "%s (%d built, %d reused; host window %d)%s", n, n_chunks,
        chunk_rows, layout, f", {n_dev}-device mesh" if mesh else "",
        spill_dir, len(missing), len(owned) - len(missing),
        store.host_max_resident,
        (f", host {fctx.host_id}/{fctx.n_hosts} shard "
         f"{len(owned)} chunks") if local_ids is not None else "")
    return ChunkedBatch(chunks=[None] * n_chunks, dim=dim, n=n,
                        chunk_rows=chunk_rows, layout=layout, mesh=mesh,
                        store=store, offsets_host=offsets_host,
                        local_chunk_ids=local_ids, schedule=schedule)
