"""Per-entity linear subspace projection for sparse random-effect shards.

Reference counterpart: ``LinearSubspaceProjector`` / ``ProjectorType``
(photon-api ``com.linkedin.photon.ml.projector`` [expected paths, mount
unavailable — see SURVEY.md §2.4]).

Purpose (same as the reference): a random-effect feature shard may be
wide (10⁴⁺ features), but each entity only ever sees a few dozen of
them — so each entity's local problem is solved in the subspace of
features it actually observed, making per-entity coefficient vectors
tiny and vmapped solves dense.

TPU translation: projection happens ONCE, in the host ETL.  For each
entity, the distinct global feature ids it saw become its subspace
(``feature_ids [E, p]``, padded); its examples' sparse entries are
remapped to local column indices and densified into [cap, p] blocks.
Device-side training never sees the global width.  ``project_back``
scatters learned local coefficients into global-width rows for model
export/scoring against new data.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from photon_ml_tpu.game.dataset import EntityGrouping


@dataclasses.dataclass
class SubspaceProjection:
    """Per-bucket per-entity subspaces for one random-effect shard.

    ``feature_ids[b]`` is [E_b, p_b] int32: global feature id of each
    local column (−1 padding).  ``local_dim[b]`` = p_b.  ``native`` and
    ``workers`` say who built it: 1 and the threads of the native
    library's passes, or 0 and 1 for the numpy body and for a
    projection read back from a saved model.
    """

    feature_ids: list[np.ndarray]
    global_dim: int
    native: int = 0
    workers: int = 1

    def local_dim(self, bucket: int) -> int:
        return self.feature_ids[bucket].shape[1]

    def counts(self, grouping: EntityGrouping) -> dict:
        """What a ``re_project`` stage says of its result: the
        entities' subspace widths summed (``subspace_columns``), the
        elements of the per-entity design matrices, rows x width each
        (``design_elements``), those of the dense blocks that hold
        them, every entity of a bucket at the bucket's capacity and
        widest subspace (``block_elements``), and that widest width of
        all (``widest``)."""
        subspace = design = block = 0
        entity_at = grouping.entity_row_map()
        for b, fids in enumerate(self.feature_ids):
            rows = np.asarray(grouping.entity_counts, np.int64)[
                entity_at[b, :len(fids)]]
            width = (fids >= 0).sum(axis=1)
            subspace += int(width.sum())
            design += int((rows * width).sum())
            block += fids.size * int(grouping.capacities[b])
        return {"entities": int(grouping.n_total_entities),
                "buckets": len(self.feature_ids),
                "subspace_columns": subspace, "design_elements": design,
                "block_elements": block,
                "widest": max((f.shape[1] for f in self.feature_ids),
                              default=0),
                "native": self.native, "workers": self.workers}

    def project_back(self, bucket: int, w_local: np.ndarray) -> list[
            tuple[np.ndarray, np.ndarray]]:
        """[E_b, p_b] local coefficients → per-entity sparse global rows
        (col_ids, values) — the reference's model-export direction."""
        fids = self.feature_ids[bucket]
        out = []
        for e in range(fids.shape[0]):
            valid = fids[e] >= 0
            out.append((fids[e][valid], np.asarray(w_local[e])[valid]))
        return out


def build_subspace_projection(
    grouping: EntityGrouping,
    rows: list[tuple[np.ndarray, np.ndarray]],
    global_dim: int,
) -> tuple[SubspaceProjection, list[np.ndarray]]:
    """Build per-entity subspaces + projected dense feature blocks.

    Args:
      grouping: entity grouping of the n examples.
      rows: per-example sparse (col_ids, values) in the GLOBAL space.
      global_dim: width of the global space.

    Returns:
      (projection, x_blocks) where ``x_blocks[b]`` is a dense
      [E_b, cap_b, p_b] array of projected features.

    The native library builds them where there is one, an entity at a
    time on every core (``native.re_project_native``), and
    ``_project_numpy`` where there is none: the same bytes either way,
    and the projection says which (``native``, ``workers``).
    """
    from photon_ml_tpu import native
    from photon_ml_tpu.data.sparse_rows import SparseRows

    rows = SparseRows.from_rows(rows)
    # Global entity index per example (stored by group_by_entity; rebuilt
    # from (bucket, slot) for groupings that predate the field).
    ex_entity = grouping.example_entity
    if ex_entity is None:
        ent_of = grouping.entity_row_map()
        ex_entity = ent_of[grouping.example_bucket, grouping.example_row]
    bucket_start = np.zeros(len(grouping.capacities) + 1, np.int64)
    np.cumsum(np.asarray(grouping.n_entities, np.int64),
              out=bucket_start[1:])
    # rank of an entity in (bucket, slot) order, and of each example's
    ent_rank = (bucket_start[np.asarray(grouping.entity_bucket)]
                + np.asarray(grouping.entity_slot))
    ex_rank = ent_rank[np.asarray(ex_entity)]

    built = native.re_project_native(
        rows.indptr, rows.cols, rows.vals, ex_rank, grouping.example_col,
        bucket_start, grouping.capacities)
    if built is not None:
        feature_ids, x_blocks, workers = built
    else:
        workers = 1
        feature_ids, x_blocks = _project_numpy(
            rows, ex_rank, grouping.example_col, bucket_start,
            grouping.capacities, global_dim)
    return SubspaceProjection(
        feature_ids=feature_ids, global_dim=global_dim,
        native=int(built is not None), workers=workers), x_blocks


def _project_numpy(rows, ex_rank, ex_pos, bucket_start, capacities,
                   global_dim) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """``native.re_project_native``'s ``feature_ids`` and ``x_blocks``
    in numpy on one thread: the fallback, and the reference the native
    builder is held to byte for byte (tests/test_native.py).

    One sort of every stored entry by (entity rank, global feature): a
    bucket's entries are then one slice, an entity's distinct features
    one run whose offsets are the features' LOCAL columns, and the
    blocks fill front to back.  One int64 key and one argsort (rows are
    canonical, so no (row, feature) repeats and ties need no order).
    All vectorized (SURVEY §7 ETL scale)."""
    n_buckets = len(capacities)
    E = int(bucket_start[-1])
    width = max(int(global_dim), int(rows.cols.max()) + 1 if rows.nnz else 1)
    if E * width >= 2 ** 63:
        raise ValueError("entities x global_dim overflows the sort key")
    per_row = np.diff(rows.indptr)
    key = np.repeat(ex_rank, per_row)
    key *= width
    key += rows.cols
    order = np.argsort(key)
    key = key[order]
    new_g = np.empty(len(key), bool)
    new_g[:1] = True
    np.not_equal(key[1:], key[:-1], out=new_g[1:])
    e_rank = key // width                  # per stored entry, sorted
    starts = np.flatnonzero(new_g)         # per distinct (entity, feature)
    g_rank = e_rank[starts]
    g_col = (key[starts] - g_rank * width).astype(np.int32)
    feat_count = np.bincount(g_rank, minlength=E)   # by rank
    feat_start = np.zeros(E + 1, np.int64)
    np.cumsum(feat_count, out=feat_start[1:])
    e_loc = np.cumsum(new_g)               # the run's number, from 1
    e_loc -= 1 + feat_start[e_rank]        # -> the feature's local column
    g_loc = e_loc[starts]
    entry_at = np.searchsorted(key, bucket_start * width)
    group_at = feat_start[bucket_start]
    del key, new_g
    e_pos = np.repeat(np.asarray(ex_pos), per_row)[order]
    e_val = rows.vals[order]
    del order

    feature_ids = []
    x_blocks = []
    for b in range(n_buckets):
        lo, hi = bucket_start[b], bucket_start[b + 1]
        ne = int(hi - lo)
        p = max(int(feat_count[lo:hi].max()) if ne else 1, 1)
        fids = np.full((ne, p), -1, np.int32)
        g = slice(group_at[b], group_at[b + 1])
        fids.reshape(-1)[(g_rank[g] - lo) * p + g_loc[g]] = g_col[g]
        feature_ids.append(fids)

        cap = capacities[b]
        xb = np.zeros((ne, cap, p), np.float32)
        s = slice(entry_at[b], entry_at[b + 1])
        at = e_rank[s] - lo
        at *= cap
        at += e_pos[s]
        at *= p
        at += e_loc[s]
        xb.reshape(-1)[at] = e_val[s]
        x_blocks.append(xb)
    return feature_ids, x_blocks
