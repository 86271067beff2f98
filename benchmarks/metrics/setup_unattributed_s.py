"""Set-up record: ``setup_s`` less every second that has a phase, the check
that the stamps tile. In a train cell the benchmark's own loop (batch,
ahead-of-time compile, warm steps) is not the program's to stamp and lies
here. One of the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "unattributed")
