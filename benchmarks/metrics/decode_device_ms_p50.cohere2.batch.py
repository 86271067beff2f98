"""Median device time of one run of ``engine_decode`` in the trace (the
program's event on the device's ``XLA Modules`` line), whatever its rung:
``decode_device_ms_p50.batch``'s reader for the Command A+ cells."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.device_ms_p50(ctx, "jit_engine_decode")
