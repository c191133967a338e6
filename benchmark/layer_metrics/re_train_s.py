"""Seconds the traced fit's thread spent in the ``photon/coord_train``
stages of random-effect coordinates (fenced: they end when the
per-entity solves have).  A random effect's stage is told by the
``buckets`` it carries; nothing to read where no stage carries it (a
program that does not say what shape its solves had)."""

from benchmark.harness import host_spans, trace_reduce


def read(ctx):
    found = host_spans.stages(ctx)
    events = [e for e in host_spans.named(found["thread"], "coord_train")
              if "buckets" in found["counts"][e]] if found else []
    if not events:
        return None
    return trace_reduce.busy_time(events) / 1e9
