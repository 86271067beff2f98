"""1 - (prompt tokens whose prefill a preemption discarded: ``prefilled`` of
the window's ``preempt`` events) / (prompt tokens of every prefill launch in
the window: ``tokens`` of the ``launch`` slices of prefill programs)."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.prefill_useful_ratio(progtrace.sliced_rows(ctx))
