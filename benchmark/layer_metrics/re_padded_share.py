"""Share of the projected random effects' dense blocks that is padding:
1 - ``design_elements`` / ``block_elements``, both summed over the
traced fit's ``photon/re_project`` stages.  An entity's design matrix
is its rows by the columns it saw; its block is its bucket's capacity
by its bucket's widest subspace.  A count of the pattern, the same in
every seed."""

from benchmark.harness import host_spans


def read(ctx):
    found = host_spans.stages(ctx)
    counts = [found["counts"][e]
              for e in host_spans.named(found["thread"], "re_project")
              if found["counts"][e].get("block_elements")] if found else []
    if not counts:
        return None
    return 100.0 * (1.0 - sum(c["design_elements"] for c in counts)
                    / sum(c["block_elements"] for c in counts))
