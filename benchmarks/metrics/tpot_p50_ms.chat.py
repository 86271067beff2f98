"""Median of what tpot_p90_ms is the tail of."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.client_percentile(ctx, "tpot", 50)
