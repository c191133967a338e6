"""Programs the backend was asked for before the window (compiled or
loaded from the persistent cache): ``programs`` of the compile ledger's
rows of every fit before the traced one, the warm-up fit and whatever
ran outside a fit.  ``run.py``'s ``compiles`` of its ``warm_up`` line,
by the program's own count."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.before_window(ctx, "programs")
