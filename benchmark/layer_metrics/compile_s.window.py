"""Seconds of backend compilation (or persistent-cache retrieval) inside
the window, per fit.  Should be ~0: the warm-up fit compiled every
program the window runs."""


def read(ctx):
    if not ctx.get("attempted"):
        return None
    return ctx["compile_s_window"] / ctx["attempted"]
