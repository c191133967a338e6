"""Line-search trials of the traced fit's OWL-QN solves, each a whole
evaluation of the objective from the coefficients: the sum of
``ls_trials`` over the ``photon/coord_train`` stages of L1 coordinates.
It moves with the seed (the solver backtracks where the orthant
projection bends the step), and ``fit_s`` with it."""

import os

from benchmark.harness import manifest as manifests

passes = manifests.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "owlqn_forward_passes.py"))


def read(ctx):
    trials = passes.counts(ctx, "ls_trials")
    return float(sum(trials)) if trials else None
