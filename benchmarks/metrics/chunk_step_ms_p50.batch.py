"""Median length of the engine steps inside the window that carried a prefill
(a chunk or an admission) beside the decode."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.step_ms_p50(ctx, with_chunk=True)
