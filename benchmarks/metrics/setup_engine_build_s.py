"""Set-up record: ``engine_build`` (``DecodeEngine.__init__``: pool, tables,
programs) plus ``warm_decode`` (the ladder dispatched), less the
``first_dispatch`` records inside them. One of the eight that tile
``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "engine_build", "warm_decode")
