"""Seconds of lowering jaxprs to MLIR modules before the window:
``lower_s`` of the compile ledger's rows of every fit before the traced
one."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.before_window(ctx, "lower_s")
