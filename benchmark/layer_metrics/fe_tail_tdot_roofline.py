"""The tail's X^T r contractions' share of the chip's HBM bandwidth:
``fe_tail_dot_roofline.py`` says how both directions are counted."""

import os

from benchmark.harness import manifest as manifests

HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx):
    return manifests.load_module(os.path.join(
        HERE, "fe_tail_dot_roofline.py")).share(ctx, "tdot")
