"""Mean pages the prefix index pins (row key ``pages_pinned``) over the
step-log rows inside the window."""

import statistics

from benchmarks.metrics import _common


def read(ctx):
    vals = [r["pages_pinned"] for r in _common.rows_in_window(ctx)
            if "pages_pinned" in r]
    return statistics.fmean(vals) if vals else None
