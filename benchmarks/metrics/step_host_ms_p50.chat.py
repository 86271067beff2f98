"""Median host time of the engine steps inside the window that decode: the
row's length less its ``fetch`` slices (the host waiting for the device).
Beside ``decode_step_ms_p50`` (timed from outside the device call) and
``decode_device_ms_p50`` (the device's own time)."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.step_host_ms_p50(ctx)
