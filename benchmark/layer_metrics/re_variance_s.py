"""Seconds the traced fit's thread spent in ``photon/re_variances``:
the random effects' SIMPLE variances, 1 / diag H of every entity's
objective at its coefficients (the prior's precision included), vmapped
by bucket on the device; the stage ends when they are computed.  It
lies inside ``photon/export_model``.  Nothing to read where no stage of
that name ran: no random effect asked for variances, or a program
without the stage."""

from benchmark.harness import host_spans


def read(ctx):
    return host_spans.wall_s(ctx, "re_variances")
