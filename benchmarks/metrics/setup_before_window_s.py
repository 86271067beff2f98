"""Set-up record: ``ready`` (the replica's ``__init__`` ends) to the
window's opening, less the ``first_dispatch`` records inside: the harness's
warm groups running on programs already built, and the traffic file's
``lead_in_s``. One of the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "before_window")
