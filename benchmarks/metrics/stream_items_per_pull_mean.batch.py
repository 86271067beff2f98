"""Stream records: ``sum(items) / sum(pulls)`` over the cell's requests.
1.0 = every token travels alone, the consumer keeps up; the last pull of a
stream may come back empty with its end, which reads just under 1."""

from benchmarks.metrics import _stream


def read(ctx):
    return _stream.items_per_pull(ctx)
