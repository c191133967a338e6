"""Time of the traced fit in which some operation ran on the device
(the union of the device-op intervals, averaged over the chips used)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    return trace["busy_ns"] / 1e6
