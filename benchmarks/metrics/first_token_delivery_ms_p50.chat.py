"""Stream records: ``first_deliver_s``, from the ``put`` of the first
delivery's oldest token to its acknowledgement (the pull's wake-up, the
``next_chunks`` reply, the proxy's socket write); median over the window's
requests."""

from benchmarks.metrics import _stream


def read(ctx):
    return _stream.median_ms(ctx, lambda r: r.get("first_deliver_s"))
