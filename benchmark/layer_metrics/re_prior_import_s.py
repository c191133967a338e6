"""Seconds the traced fit's thread spent in ``photon/re_prior_import``:
mapping the warm-start model's means and SIMPLE variances of each
random effect onto this fit's entities and subspaces (one sorted join
on (entity, global column) keys a bucket pair), as each entity's prior.
Nothing to read where no stage of that name ran: a fit with no prior
for its random effects, or a program without the stage."""

from benchmark.harness import host_spans


def read(ctx):
    return host_spans.wall_s(ctx, "re_prior_import")
