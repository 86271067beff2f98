"""Mean ``pages`` slice (``_ensure_decode_pages``: page allocation for the
next token of every slot, preemption included) over the window's steps."""

import statistics

from benchmarks import progtrace


def read(ctx):
    rows = progtrace.sliced_rows(ctx)
    return statistics.fmean(progtrace.slice_ms(r, ("pages",))
                            for r in rows) if rows else None
