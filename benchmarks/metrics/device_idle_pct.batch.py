"""1 - union of device-operation intervals over the traced span, from the
profiler trace taken inside the window."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.device_idle_pct(ctx)
