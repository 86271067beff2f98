"""Set-up record: the ``weights`` phases, the host's time to build and
dispatch the initialisation of the weights (a replica's ``init_params`` and
``compute_weights``; a trainer's ``init_sharded_params`` and
``init_optimizer_state``). Host time: what the device still owes is paid
where the host next waits. One of the eight that tile ``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "weights")
