"""Seconds the traced fit's thread spent in ``photon/group_entities``,
summed over the random-effect coordinates: grouping rows by entity,
projecting a sparse shard into per-entity subspaces
(``re_projection_s`` is that part) and scattering labels, weights and
index maps into the per-bucket host blocks.  The same stage as
``entity_grouping_s``, which lists its own cells."""

from benchmark.harness import host_spans


def read(ctx):
    return host_spans.wall_s(ctx, "group_entities")
