"""Share of the traced span in which the device is idle (gaps >= 0.5 ms)
and no ``engine:`` slice covers the gap, once the device's clock is put on
the host's from the launch/fetch brackets: the check that the two meet."""

from benchmarks import progtrace


def read(ctx):
    return progtrace.unattributed_pct(ctx)
