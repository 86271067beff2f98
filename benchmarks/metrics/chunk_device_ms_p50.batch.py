"""Median device time of one run of ``engine_paged_suffix`` whose ``launch``
slice says ``prefill_chunk`` (the chunked-prefill continuation)."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.device_ms_p50(ctx, "jit_engine_paged_suffix",
                                   role="prefill_chunk")
