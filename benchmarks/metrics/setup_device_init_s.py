"""Set-up record: the ``device_init`` phase, the worker imports JAX and what
it runs and opens its chips (``jax.devices()``). One of the eight that tile
``setup_s``."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "device_init")
