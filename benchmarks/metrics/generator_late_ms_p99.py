"""How late the load generator sent: send time minus due time, 99th percentile
over the window's requests. A starved generator must not read as a fast
server."""

from benchmarks.metrics import _common


def read(ctx):
    from benchmarks import stats

    late = [(o.sent - o.due) * 1e3 for o in _common.measured(ctx)]
    return stats.percentile(late, 99) if late else None
