"""From a profiler trace to numbers.

Every function here takes plain lists of ``(start, duration, name)``
events (any one time unit; the trace's is nanoseconds), so the tests
need no ``.xplane.pb``.  ``read_xplane`` is the only part that touches
the profiler's file format.
"""

import re

NAME_CHARS = 120
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_DETAIL = re.compile(r'(?:custom_call_target="|kind=)([\w.\-]+)')


def busy_intervals(events, lo=None, hi=None):
    """The union of the events' intervals, clipped to [lo, hi], as a
    sorted list of disjoint (start, end)."""
    spans = []
    for start, duration, _name in events:
        end = start + duration
        if lo is not None:
            start = max(start, lo)
        if hi is not None:
            end = min(end, hi)
        if end > start:
            spans.append((start, end))
    spans.sort()
    merged = []
    for start, end in spans:
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_time(events, lo=None, hi=None):
    """Time in which at least one event ran: overlapping and nested
    events count once."""
    return sum(end - start for start, end in busy_intervals(events, lo, hi))


def sum_by_name(events):
    """name -> summed *self* time: an event's duration less that of the
    events nested directly inside it on the same line (a ``while`` that
    encloses its body's operations keeps only what no child covers), so
    the names add up to the busy time and not to several times it.
    Events that overlap without nesting each keep their whole length."""
    order = sorted(events, key=lambda e: (e[0], -e[1]))
    totals = {}
    stack = []  # [end, name, self_time]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, self_time = stack.pop()
            totals[name] = totals.get(name, 0) + max(self_time, 0)

    for start, duration, name in order:
        close(start)
        end = start + duration
        if stack and end <= stack[-1][0]:
            stack[-1][2] -= duration
        stack.append([end, name, duration])
    close(float("inf"))
    return totals


def top(totals, k):
    """The ``k`` largest entries of a name -> number mapping, as
    [[name, number], ...], largest first (ties by name)."""
    return [[name, value] for name, value in
            sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))[:k]]


def short_name(name):
    """A name for the breakdown, at most NAME_CHARS long.  On a TPU the
    trace names an operation by its whole HLO text, kilobytes for a
    kernel with its operands and layouts (my chip run, PR 25):
    ``%body.190 = f32[10140,16,128]{...} custom-call(...),
    custom_call_target="tpu_custom_call", ...`` becomes ``%body.190
    custom-call:tpu_custom_call f32[10140,16,128]{...}``: the
    instruction, its opcode with the call's target or the fusion's
    kind, and the (first) result shape.  Any other name is cut."""
    head, equals, rest = name.partition(" = ")
    if not equals:
        return name[:NAME_CHARS]
    opcode, detail = _OPCODE.search(rest), _DETAIL.search(rest)
    what = opcode.group(1) if opcode else ""
    if detail:
        what += ":" + detail.group(1)
    shape = rest.split(" ", 1)[0].rstrip(",")
    return " ".join(part for part in (head, what, shape) if part)[:NAME_CHARS]


def idle_gaps(events, lo, hi, host_spans=(), k=None):
    """The gaps of [lo, hi] in which no event ran, longest first (the
    ``k`` longest, or all), each as (start, length, label).  The label
    is the name of the shortest of ``host_spans`` that holds the gap's
    middle: what the host was in while the device waited; ``None``
    where no span does.  Only the gaps returned are labelled: a trace
    has a gap after nearly every operation."""
    gaps = []
    at = lo
    for start, end in busy_intervals(events, lo, hi):
        if start > at:
            gaps.append((at, start - at))
        at = end
    if hi > at:
        gaps.append((at, hi - at))
    out = []
    for start, length in sorted(gaps, key=lambda g: (-g[1], g[0]))[:k]:
        mid = start + length / 2
        holding = [(d, name) for s, d, name in host_spans
                   if s <= mid <= s + d]
        out.append((start, length, min(holding)[1] if holding else None))
    return out


def summarize(planes, annotation, chips, k):
    """What the layer metrics and the breakdown read of a trace, from
    ``read_xplane``'s planes: the traced operation's interval (the
    longest host event named ``annotation``), the device operations
    inside it, busy time averaged over the chips, the ``k`` device
    operations with most self time (seconds, ``short_name``d: the time
    is summed by the whole name) and the ``k`` longest idle
    gaps of the first chip, each named ``<what the host thread was
    in>@<seconds into the operation>``."""
    device = planes["device"]
    if not any(device.values()):
        raise RuntimeError("the trace holds no device operation")
    marks = [(e, events) for events in planes["host"].values()
             for e in events if e[2] == annotation]
    if not marks:
        raise RuntimeError(f"no {annotation!r} annotation in the trace")
    (lo, length, _), host_spans = max(marks, key=lambda m: m[0][1])
    hi = lo + length
    inside = {plane: [e for e in events if e[0] + e[1] > lo and e[0] < hi]
              for plane, events in device.items()}
    by_name = {}
    for events in inside.values():
        for name, ns in sum_by_name(events).items():
            by_name[name] = by_name.get(name, 0) + ns / chips
    gaps = idle_gaps(inside[min(inside)], lo, hi, host_spans, k)
    return {
        "device_events": inside, "interval": (lo, hi),
        "busy_ns": sum(busy_time(events, lo, hi)
                       for events in inside.values()) / chips,
        "window_ns": hi - lo,
        "device_ops": [[short_name(name), ns / 1e9]
                       for name, ns in top(by_name, k)],
        "idle_gaps": [[f"{label or 'outside_' + annotation}"
                       f"@{(start - lo) / 1e9:.3f}s", span / 1e9]
                      for start, span, label in gaps],
        "n_device_events": sum(len(v) for v in inside.values()),
    }


def read_xplane(path):
    """{"device": {plane name: events}, "host": {line name: events}}
    from an ``.xplane.pb``, events as (start_ns, duration_ns, name).

    Device events are the ``XLA Ops`` line of each ``/device:TPU:<i>``
    plane: one event per executed HLO operation (a Mosaic kernel is
    one such operation), named on a v5e by its whole HLO text (my chip
    run, PR 25).  Host events are every line of the ``/host:CPU``
    plane, keyed by the line's (thread's) name."""
    from jax.profiler import ProfileData

    out = {"device": {}, "host": {}}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["device"].setdefault(plane.name, []).extend(
                        (e.start_ns, e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                out["host"][f"{line.name}#{i}"] = [
                    (e.start_ns, e.duration_ns, e.name) for e in line.events]
    return out
