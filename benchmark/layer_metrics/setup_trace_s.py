"""Seconds of tracing (outermost ``jaxpr_trace_duration`` events: a trace
inside a trace is counted once) before the window: ``trace_s`` of the
compile ledger's rows of every fit before the traced one."""

from benchmark.harness import compile_path


def read(ctx):
    return compile_path.before_window(ctx, "trace_s")
