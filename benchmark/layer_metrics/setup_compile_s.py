"""Seconds of backend compilation before the window, a cache hit's
retrieval taken out: ``compile_s`` of the compile ledger's rows of
every fit before the traced one.  Not 0 on a warm machine: a program
that compiles in less than the persistent cache's floor is compiled
anew in every process."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.before_window(ctx, "compile_s")
