"""Percentage of the random effects' real coefficients (the columns
their entities saw, padding left out) that carry a prior from the
warm-start model: ``coefficients_with_prior`` over ``coefficients``,
both summed over the traced fit's ``photon/re_prior_import`` stages.
A count of the two days' patterns, the same in every seed.  Nothing to
read where no such stage ran."""

from benchmark.harness import host_spans


def read(ctx):
    found = host_spans.stages(ctx)
    counts = [found["counts"][e]
              for e in host_spans.named(found["thread"], "re_prior_import")
              if found["counts"][e].get("coefficients")] if found else []
    if not counts:
        return None
    return 100.0 * (sum(c["coefficients_with_prior"] for c in counts)
                    / sum(c["coefficients"] for c in counts))
