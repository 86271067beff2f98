"""Median host bookkeeping of admission (slices ``reap`` + ``admit`` +
``pages``, without the device calls they bracket) over the steps inside the
window that admit a wave or run a prefill chunk."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.median_ms([
        progtrace.slice_ms(r, ("reap", "admit", "pages"))
        for r in progtrace.sliced_rows(ctx)
        if any(p["phase"] in ("admit", "prefill_chunk")
               for p in r["phases"])])
