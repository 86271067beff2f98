"""Mean active slots over the step-log rows inside the window."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.active_slots_mean(ctx)
