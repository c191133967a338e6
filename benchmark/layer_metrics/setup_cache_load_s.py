"""Seconds of retrieving executables from the persistent compilation
cache before the window: ``cache_load_s`` of the compile ledger's rows
of every fit before the traced one."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.before_window(ctx, "cache_load_s")
