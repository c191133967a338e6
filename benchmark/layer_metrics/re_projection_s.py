"""Seconds the traced fit's thread spent in ``photon/re_project``,
summed over the random effects with sparse shards: the sort of a
shard's entries by (entity, column), each entity's local column
numbering, and the scatter into the per-bucket dense blocks.  It lies
inside ``photon/group_entities``."""

from benchmark.harness import host_spans


def read(ctx):
    return host_spans.wall_s(ctx, "re_project")
