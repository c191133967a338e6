"""Share of the traced fit in which no operation ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
