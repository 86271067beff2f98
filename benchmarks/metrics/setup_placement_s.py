"""Set-up record: ``placement.begin`` (``serve.run`` or ``JaxTrainer.fit``
is entered) to ``placement.end`` (a worker holds the lease and the class or
loop function, and is about to run it), less anything inside that has a
phase of its own. One of the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "placement")
