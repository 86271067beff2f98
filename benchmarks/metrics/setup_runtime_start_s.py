"""Set-up record: the benchmark process's start to the end of the program's
``runtime_start`` phase (``ray_tpu.init`` returns: controller and node up,
chips probed), so the harness's imports before ``init`` are in it. One of
the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "runtime_start")
