"""Stream records: a request's mean ``put`` -> acknowledged time over its
deliveries after the first, ``(deliver_s_sum - first_deliver_s) / (acked -
1)``; median over the cell's requests."""

from benchmarks.metrics import _stream


def read(ctx):
    return _stream.median_ms(ctx, _stream.token_delivery_s)
