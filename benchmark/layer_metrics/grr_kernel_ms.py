"""Device time of the GRR Mosaic kernels (``ops/grr_kernel.py``) in the
traced fit: the summed durations of the device operations that are
Mosaic kernels, averaged over the chips used.  The GRR kernels are the
only Mosaic kernels the training path compiles for a TPU (the other
``pallas_call`` of the program, in ``ops/kernels.py``, runs in interpret
mode only).

A v5e's trace names a device operation by its whole HLO text, and a
Mosaic kernel's says ``custom_call_target="tpu_custom_call"`` (my chip
run, PR 25); what comes before `` = `` says nothing: XLA names the
operation after the jitted function or loop body that holds it
(``%body.190``).  Another custom call, such as a sort, has another
target.  Where no operation says so there is nothing to read and the
metric is left out of the line: either the fixed effect did not run the
GRR layout, or the profiler names operations otherwise."""

MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    found = [duration for events in trace["device_events"].values()
             for _start, duration, name in events if MOSAIC_MARK in name]
    if not found:
        return None
    return sum(found) / ctx["chips"] / 1e6
