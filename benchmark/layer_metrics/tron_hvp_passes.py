"""Hessian-vector products of the traced fit's fixed-effect TRON
solves: the sum of ``hvp_passes`` over the ``photon/coord_train``
stages of one solve (told by the ``solver_iterations`` they carry; a
random effect's stage carries its lanes' sums under other names).  A
solve makes one product a conjugate-gradient step and one an outer
iteration for its ratio: ``cg_steps + solver_iterations``.  Nothing to
read where no such stage carries the count (an L-BFGS or OWL-QN solve,
or a program whose TRON counts nothing)."""

from benchmark.harness import host_spans


def read(ctx):
    found = host_spans.stages(ctx)
    events = host_spans.named(found["thread"], "coord_train") \
        if found else []
    passes = [found["counts"][e]["hvp_passes"] for e in events
              if "solver_iterations" in found["counts"][e]
              and "hvp_passes" in found["counts"][e]]
    return float(sum(passes)) if passes else None
