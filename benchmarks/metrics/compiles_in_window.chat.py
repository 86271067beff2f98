"""Backend compiles of the process that holds the chip, counted by
compile_watch between the window's edges; expected 0."""

from benchmarks.metrics import _common


def read(ctx):
    return _common.compiles_in_window(ctx)
