"""Device-mesh utilities: the TPU replacement for the Spark cluster.

Reference counterpart: Spark's runtime substrate — executors, torrent
broadcast, hash partitioning (SURVEY.md §5.8 [reference mount
unavailable]).  The mapping:

- executor set            → ``jax.sharding.Mesh`` over TPU chips (ICI)
- ``broadcast(w)``        → replicated sharding ``P()`` (a no-op: every
                            chip holds w; XLA keeps it resident in HBM)
- ``partitionBy`` shuffle → a one-time host-side layout into batch shards
                            (``shard_batch``), then static placement
- ``treeAggregate``       → ``lax.psum`` over the mesh axis, riding ICI

Axis names: ``"data"`` for example-parallelism (fixed effect) and
``"entity"`` for entity-sharded random effects.  Multi-host scale-out
uses the same meshes over ``jax.distributed``-initialized device sets —
collectives then span DCN between slices with no code change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.batch import Batch

DATA_AXIS = "data"
ENTITY_AXIS = "entity"


def _make_mesh(axis: str, n_devices: int | None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devs)}"
            )
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def data_parallel_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all)."""
    return _make_mesh(DATA_AXIS, n_devices)


def entity_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh named for entity sharding (random effects): bucket
    blocks [E_b, cap, p] shard their leading (entity) axis here —
    the reference's parallelism strategy #2 (SURVEY §2.3)."""
    return _make_mesh(ENTITY_AXIS, n_devices)


def shard_entity_blocks(blocks: list, mesh: Mesh) -> list:
    """Pad each bucket's entity count to the mesh size and shard the
    leading axis on ENTITY_AXIS.  Padding entities carry zero
    data/mask, so their (vmapped) solves converge immediately and their
    coefficients are never gathered.  Per-device entity counts are
    exactly balanced by construction."""
    n_dev = mesh.devices.size
    out = []
    for b in blocks:
        e = b.shape[0]
        e_pad = padded_rows(max(e, 1), n_dev)
        if e_pad != e:
            b = jnp.pad(b, ((0, e_pad - e),) + ((0, 0),) * (b.ndim - 1))
        out.append(jax.device_put(b, NamedSharding(mesh, P(ENTITY_AXIS))))
    return out


def place_entity_chunk(arrays: dict, mesh: Mesh | None) -> dict:
    """Host entity-chunk leaves (name → [C, ...] ndarray) → device,
    entity-axis sharded when a mesh is given — the streamed random-
    effect coordinate's per-chunk placement (ISSUE 5).  ``C`` must be a
    multiple of the mesh size (the streamed builder rounds
    ``re_chunk_entities`` up), so every device holds an equal slice of
    the chunk's vmapped solve lanes; padding entities carry zero mask
    and converge immediately, exactly as in ``shard_entity_blocks``."""
    if mesh is None:
        return jax.device_put(arrays)
    n_dev = mesh.devices.size
    for k, a in arrays.items():
        if a.shape[0] % n_dev != 0:
            raise ValueError(
                f"entity chunk leaf '{k}' has {a.shape[0]} entities, "
                f"not divisible by mesh size {n_dev}; round the chunk "
                "size up to the mesh grid")
    sharding = NamedSharding(mesh, P(ENTITY_AXIS))
    return {k: jax.device_put(np.ascontiguousarray(a), sharding)
            for k, a in arrays.items()}


def batch_spec() -> P:
    """PartitionSpec sharding the example axis (every Batch leaf has the
    example dimension leading)."""
    return P(DATA_AXIS)


def replicated_spec() -> P:
    return P()


def shard_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Place a host-built batch onto the mesh, example-axis sharded.

    The batch must already be padded so n divides the mesh size
    (``make_*_batch(pad_to=...)``); padding rows are masked, so shard
    imbalance costs nothing but the pad FLOPs.  This is the rebuild's
    "shuffle": it happens once, before training, not per-iteration.
    """
    from photon_ml_tpu.data.batch import SparseBatch

    if isinstance(batch, SparseBatch) and (
        batch.colmajor is not None or batch.grr is not None
    ):
        raise ValueError(
            "cannot shard a SparseBatch whose colmajor/GRR layout was "
            "built globally: its index arrays reference the whole "
            "batch, but each device shard sees only its local "
            "residuals.  Build with shard_sparse_batch(...) instead, "
            "which constructs per-shard layouts."
        )
    n = batch.n_padded
    n_dev = mesh.devices.size
    if n % n_dev != 0:
        raise ValueError(
            f"batch rows {n} not divisible by mesh size {n_dev}; "
            f"build the batch with pad_to=ceil(n/{n_dev})*{n_dev}"
        )
    sharding = NamedSharding(mesh, batch_spec())
    return jax.tree.map(lambda a: jax.device_put(a, sharding), batch)


def shard_sparse_batch(
    rows,
    dim: int,
    labels: np.ndarray,
    mesh: Mesh,
    weights: np.ndarray | None = None,
    offsets: np.ndarray | None = None,
    row_capacity: int | None = None,
    col_major: bool = True,
    col_capacity: int | None = None,
    layout: str | None = None,
    cache_dir: str | None = None,
):
    """Host-side ETL: split examples across the mesh, build one
    SparseBatch per device — each with the fast-contraction layout of
    *its own* rows — and assemble the global example-sharded arrays.

    This is the rebuild of the reference's one-time ``partitionBy``
    shuffle (SURVEY.md §5.8): after this call every optimizer iteration
    is pure compute + one ``psum``; no per-step data movement.  The
    per-shard layout is what keeps the gradient contraction scatter-free
    under data parallelism: each device computes ``Xᵀ_shard r_shard``
    locally and the partial [dim] gradients are combined by the same
    ``psum`` that already reduces the loss.

    ``layout`` selects the per-shard contraction layout:
    - ``"grr"`` — per-device compiled GRR plans run by the Mosaic
      kernel (``data.grr.build_sharded_grr_pairs``): the fast TPU path,
      now also the distributed path (BASELINE.json north star);
    - ``"colmajor"`` (default, = ``col_major=True``) — per-shard
      transposed-ELL copies;
    - ``"ell"`` (= ``col_major=False``) — plain ELL shards.

    ``cache_dir``: on-disk GRR plan cache (``photon_ml_tpu.cache``) for
    the per-shard plans — the one-time "shuffle" becomes one-time per
    DATASET, not per run.
    """
    from photon_ml_tpu.data.batch import make_sparse_batch
    from photon_ml_tpu.data.colmajor import build_colmajor, choose_capacity
    from photon_ml_tpu.data.grr import collect_spill_warnings
    from photon_ml_tpu.data.sparse_rows import SparseRows

    if layout is None:
        layout = "colmajor" if col_major else "ell"
    if layout not in ("grr", "colmajor", "ell"):
        raise ValueError(f"unknown layout {layout!r}")
    col_major = layout == "colmajor"

    n = len(labels)
    n_dev = mesh.devices.size
    per = padded_rows(n, n_dev) // n_dev
    if row_capacity is not None:
        k = row_capacity
    elif isinstance(rows, SparseRows):
        k = max(rows.max_nnz, 1)
    else:
        k = max((len(c) for c, _ in rows), default=1)

    weights = np.ones(n) if weights is None else np.asarray(weights)
    offsets = np.zeros(n) if offsets is None else np.asarray(offsets)

    shards = []
    # One spill-warning aggregation scope over the whole sharded build
    # (per-shard batch builds + the sharded plan set below): one
    # summary line per build, never one per shard sub-plan (a line a
    # sub-plan buries the end of the log an operator reads).
    with collect_spill_warnings():
        for i in range(n_dev):
            lo, hi = i * per, min((i + 1) * per, n)
            shards.append(
                make_sparse_batch(
                    rows[lo:hi],
                    dim,
                    np.asarray(labels)[lo:hi],
                    weights=weights[lo:hi],
                    offsets=offsets[lo:hi],
                    row_capacity=k,
                    pad_to=per,
                )
            )

        if col_major:
            if col_capacity is None:
                if isinstance(rows, SparseRows):
                    all_cols = rows.cols
                else:
                    all_cols = (
                        np.concatenate([np.asarray(c) for c, _ in rows])
                        if len(rows) else np.zeros(0, np.int64)
                    )
                counts = np.bincount(all_cols, minlength=dim)
                col_capacity = choose_capacity(counts)
            # Per-shard virtual-row counts (cheap bincounts) → common
            # padded shape, so build_colmajor emits equal-shape shards
            # directly.
            shard_counts = [
                np.bincount(
                    np.asarray(b.col_ids).reshape(-1)[
                        np.asarray(b.values).reshape(-1) != 0
                    ],
                    minlength=dim,
                )
                for b in shards
            ]
            from photon_ml_tpu.ops.kernels import vrow_pad

            v_max = max(
                int((-(-c // col_capacity)).sum()) for c in shard_counts
            )
            v_max = vrow_pad(v_max, None)
            shards = [
                b.replace(colmajor=build_colmajor(
                    np.asarray(b.col_ids), np.asarray(b.values), dim,
                    capacity=col_capacity, pad_vrows_to=v_max,
                ))
                for b in shards
            ]
        elif layout == "grr":
            from photon_ml_tpu.data.grr import build_sharded_grr_pairs

            pairs = build_sharded_grr_pairs(
                [np.asarray(b.col_ids) for b in shards],
                [np.asarray(b.values) for b in shards],
                dim,
                cache_dir=cache_dir,
            )
            shards = [b.replace(grr=p) for b, p in zip(shards, pairs)]

    devices = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, batch_spec())

    def assemble(*leaves):
        placed = [jax.device_put(lf, d) for lf, d in zip(leaves, devices)]
        gshape = (n_dev * leaves[0].shape[0],) + tuple(leaves[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, placed
        )

    return jax.tree.map(assemble, *shards)


def replicate(x, mesh: Mesh):
    """Replicate an array (the coefficient 'broadcast')."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def padded_rows(n: int, n_devices: int) -> int:
    """Smallest multiple of n_devices ≥ n."""
    return ((n + n_devices - 1) // n_devices) * n_devices
