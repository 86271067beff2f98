"""Engine clocks: admitted_at - submitted_at, mean over the window's requests."""

from benchmarks.metrics import _common


def read(ctx):
    import statistics

    out = []
    for o in _common.measured(ctx):
        c = ctx["clocks"].get(o.request_id)
        if c and c[1] is not None:
            out.append((c[1] - c[0]) * 1e3)
    return statistics.fmean(out) if out else None
