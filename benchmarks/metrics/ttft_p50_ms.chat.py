"""Median of what ttft_p90_ms is the tail of: the steadier statistic beside it."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.client_percentile(ctx, "ttft", 50)
