"""Set-up record: the ``probe`` phase, ``tpu.detect_chip_count``'s subprocess
that opens the chips to count them, its waits on a busy device included. A
PART of ``setup_runtime_start_s``, reported beside it and not added."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "probe")
