"""Median length of the whole steps inside the window, host clock closed on the
loss fetch."""


def read(ctx):
    import statistics

    return statistics.median(ctx["step_ms"]) if ctx.get("step_ms") else None
