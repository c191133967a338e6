"""The tail's X.w contractions' share of the chip's HBM bandwidth: the
bytes they must move at the least over their device time, against the
published peak (``benchmark/harness/peaks.py``).  Its twin,
``fe_tail_tdot_roofline``, reads X^T r through ``share`` below: one
direction each, because a call of the one moves other bytes than a call
of the other, and one share over both moves when only their mix does.

``least_bytes`` is the count: a call reads each tail entry's index,
segment and value (12 B) and one 4 B table element for it, and writes
its output vector once ([rows] for X.w, [dim] for X^T r).  A gather
that fetches a whole line for a 4 B element moves more than this, which
is why the share is expected to be a few per cent at most; over 100 %
would mean the count is wrong.  The sizes come from the
``photon/grr_plan_build`` stage's counts; the calls and their time from
the trace: every call ends in exactly one segment-sum, the one tail
operation (``fe_tail_ms`` says how those are found) whose result is a
vector of ``rows`` numbers (X.w) or of ``dim`` (X^T r), and the tail
operations since the call before it (the index preparation, the gather,
the multiplication) are that call's.  Tail operations after a device's
last segment-sum belong to a call the trace cut off, and to neither
share."""

import os

from benchmark.harness import manifest as manifests
from benchmark.harness.peaks import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ENTRY_BYTES = 12 + 4
# what a call writes, by direction: the key of the plan build's counts
WRITES = {"dot": "rows", "tdot": "dim"}


def least_bytes(tail_nnz, written, calls):
    """Bytes that ``calls`` contractions over ``tail_nnz`` entries, each
    writing a vector of ``written`` numbers, cannot avoid moving."""
    return calls * (tail_nnz * ENTRY_BYTES + 4 * written)


def by_direction(counts, found):
    """{direction: (calls, device nanoseconds)} summed over the devices
    of ``found`` (``fe_tail_ms.tail_events``).  In HLO text the result's
    shape follows `` = ``."""
    ends = {f" = f32[{counts[key]}]": direction
            for direction, key in WRITES.items()}
    out = {direction: [0, 0.0] for direction in WRITES}
    if len(ends) < len(WRITES):     # rows == dim: the two look alike
        return out
    for events in found.values():
        pending = 0.0
        for _start, duration, name in sorted(events):
            pending += duration
            for mark, direction in ends.items():
                if mark in name:
                    out[direction][0] += 1
                    out[direction][1] += pending
                    pending = 0.0
    return out


def share(ctx, direction):
    """``direction``'s least bytes over its device time, in per cent of
    the device's published HBM bandwidth; None where no call of it was
    traced."""
    counts, found = manifests.load_module(os.path.join(
        HERE, "fe_tail_ms.py")).tail_events(ctx)
    if not found:
        return None
    calls, busy_ns = by_direction(counts, found)[direction]
    if not calls or busy_ns <= 0:
        return None
    kind = ctx.get("device_kind")
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    moved = least_bytes(counts["tail_nnz"], counts[WRITES[direction]], calls)
    return 100.0 * moved / (busy_ns / 1e9) / (peaks(kind)["hbm_gb_per_s"]
                                             * 1e9)


def read(ctx):
    return share(ctx, "dot")
