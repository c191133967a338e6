"""Forward contractions X.v that the traced fit's solves say they made:
the sum of ``forward_passes`` over the ``photon/coord_train`` stages
that carry it (a solve whose result is one ``OptimizationResult``: the
fixed effect's).  A solve that walks the margins makes ``iterations +
1``; one that evaluates every line-search trial from the coefficients
makes ``iterations + 1 + ls_trials``.  Nothing to read where no stage
carries the count (a program whose solver does not count them)."""

from benchmark.harness import host_spans


def read(ctx):
    found = host_spans.stages(ctx)
    events = host_spans.named(found["thread"], "coord_train") \
        if found else []
    passes = [found["counts"][e]["forward_passes"] for e in events
              if "forward_passes" in found["counts"][e]]
    return float(sum(passes)) if passes else None
