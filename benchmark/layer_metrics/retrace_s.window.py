"""Seconds of tracing, lowering and cache retrieval charged to the traced
fit.  Should be 0 beside ``compile_s.window``: it is what says a window
fit traced again (a fresh closure under ``jit``, a shape that moved)
where the backend's clock, on a cache hit, says nearly nothing."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.traced_window(ctx, "trace_s", "lower_s",
                                      "cache_load_s")
