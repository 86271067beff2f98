"""Median length of the engine steps inside the window that only decoded."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.step_ms_p50(ctx, with_chunk=False)
