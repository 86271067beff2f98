"""Median time of the per-slot loop after the logits arrive (slices
``sample_emit`` + ``finish``), over the steps inside the window that decode."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.median_ms([
        progtrace.slice_ms(r, ("sample_emit", "finish"))
        for r in progtrace.sliced_rows(ctx) if progtrace.decodes(r)])
