"""Set-up record: the host's time in the FIRST dispatch of every program key
before the window (trace, lower, compile or load from the cache, enqueue;
not the program's run). One of the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "first_dispatch")
