"""Trace: time of collective operations on a device during which no other
operation runs there, averaged over the devices, as a share of the traced span
times the median step: milliseconds of every step spent in collectives that
nothing hides. An asynchronous collective counts for its ``-start`` and
``-done`` instructions, not for the transfer between them."""


def read(ctx):
    import statistics

    t = ctx.get("trace")
    if t is None or not ctx.get("step_ms"):
        return None
    return (t["collective_exposed_s"] / t["window_s"]
            * statistics.median(ctx["step_ms"]))
