"""Peak device memory of the process after the window, on the fullest
chip (``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
