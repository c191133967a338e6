"""Forward contractions X.w of the traced fit's OWL-QN solves: the sum
of ``forward_passes`` over the ``photon/coord_train`` stages of L1
coordinates (told by the ``nonzero_coefficients`` they carry).  Such a
solve evaluates its start, every line-search trial and every accepted
point from the coefficients: ``1 + ls_trials + solver_iterations``.
Nothing to read where no such stage carries the count (a program whose
whole-evaluation solves count nothing)."""

from benchmark.harness import host_spans


def counts(ctx, key):
    """``key`` of every L1 coordinate's ``coord_train`` stage that
    carries it."""
    found = host_spans.stages(ctx)
    events = host_spans.named(found["thread"], "coord_train") \
        if found else []
    return [found["counts"][e][key] for e in events
            if "nonzero_coefficients" in found["counts"][e]
            and key in found["counts"][e]]


def read(ctx):
    passes = counts(ctx, "forward_passes")
    return float(sum(passes)) if passes else None
