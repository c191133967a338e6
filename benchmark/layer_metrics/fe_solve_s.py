"""Seconds the traced fit's thread spent in the fixed effect's
``photon/coord_train`` stages (fenced: they end when the solve has).
The stage of one solve, and not of a bucket of per-entity solves, is
told by the ``solver_iterations`` it carries; nothing to read where no
stage carries them."""

from benchmark.harness import host_spans, trace_reduce


def read(ctx):
    found = host_spans.stages(ctx)
    events = [e for e in host_spans.named(found["thread"], "coord_train")
              if "solver_iterations" in found["counts"][e]] if found else []
    if not events:
        return None
    return trace_reduce.busy_time(events) / 1e9
