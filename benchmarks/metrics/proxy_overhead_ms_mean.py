"""What proxy, router and replica stream add to the first token: the client's
TTFT from the send, minus the engine's own first_token_at - submitted_at of
the same request_id; mean over the window's requests."""

from benchmarks.metrics import _common


def read(ctx):
    import statistics

    out = []
    for o in _common.measured(ctx):
        c = ctx["clocks"].get(o.request_id)
        if o.ok and c and c[2] is not None:
            out.append(((o.arrivals[0] - o.sent) - (c[2] - c[0])) * 1e3)
    return statistics.fmean(out) if out else None
