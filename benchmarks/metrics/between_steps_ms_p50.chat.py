"""Median ``park`` slice (the end of one engine step to the start of the
next: the loop's own overhead, the benchmark's row copy, the wait for the
GIL) over the steps that began with slots active."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.median_ms([
        (s["t1"] - s["t0"]) * 1e3 for r in progtrace.sliced_rows(ctx)
        for s in r["slices"]
        if s["name"] == "park" and s.get("active", 0) > 0])
