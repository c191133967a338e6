"""Device time of the fixed effect's tail contractions in the traced
fit: the summed durations, averaged over the chips used, of the device
operations of ``photon_ml_tpu.data.grr.GrrTail``: for X.w a gather from
the coefficient vector and a sorted segment-sum over rows, for X^T r a
gather from the residual and a sorted segment-sum into the full-width
gradient, with the index preparation and the multiplications between
them.

How they are told from the rest.  The program runs them under
``jax.named_scope("photon/fe_tail_dot")`` and ``("photon/fe_tail_tdot")``,
but a v5e's trace names a device operation by its HLO text WITHOUT the
``metadata={op_name=...}`` that would carry the scope (my chip run, PR
30: no event of the trace says either scope).  What the text does carry
is every operand's and the result's shape, and the tail's operations
are the only ones with a vector as long as the tail's arrays: an
operation is the tail's when its text holds ``[<tail_len>]``, the length
of those arrays on the device as the program itself publishes it among
the counts of its ``photon/grr_plan_build`` stage.  So a change that
pads, cuts up or renumbers the tail keeps this reader by publishing the
new length; the entries (``tail_nnz``) stay what the roofline counts.  A
stage without ``tail_len`` is read by ``tail_nnz``, which is the arrays'
length where nothing pads them.  Where the stage has neither count, or
the tail is empty, there is nothing to read and the metric is left out
of the line."""

import os
import re

from benchmark.harness import manifest as manifests

HERE = os.path.dirname(os.path.abspath(__file__))
# In HLO text the opcode follows the result's shape: ``%while.130 =
# (f32[54686453]{0}, ...) while(...), condition=..., body=...``.
ENCLOSING = re.compile(r"[)}\]] (while|conditional|call)\(")


def tail_events(ctx):
    """(the plan build's counts, {device: [(start_ns, duration_ns,
    name)]} of the traced fit's device operations that hold the tail's
    length as a dimension, in the trace's order); (None, {}) where
    there is nothing to read.  An operation that encloses others (the
    solver's ``while`` carries the tail's arrays in its state, so its
    text holds their shapes) is left out: what it encloses is counted
    where it is the tail's."""
    counts = manifests.load_module(os.path.join(
        HERE, "tail_nnz_share.py")).plan_build_counts(ctx)
    if not counts or not counts.get("tail_nnz"):
        return None, {}
    mark = f"[{counts.get('tail_len') or counts['tail_nnz']}]"
    found = {device: [event for event in events
                      if mark in event[2] and not ENCLOSING.search(event[2])]
             for device, events in ctx["trace"]["device_events"].items()}
    return counts, {device: events for device, events in found.items()
                    if events}


def read(ctx):
    _counts, found = tail_events(ctx)
    if not found:
        return None
    return sum(duration for events in found.values()
               for _start, duration, _name in events) / ctx["chips"] / 1e6
