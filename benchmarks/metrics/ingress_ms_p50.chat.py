"""Stream records: ``submitted - received``, from the start of the proxy's
``http:<route>`` span to the engine's submit (HTTP read and parse, routing,
the ``start_stream`` actor call); median over the window's requests."""

from benchmarks.metrics import _stream


def read(ctx):
    return _stream.median_ms(ctx, _stream.between("submitted", "received"))
