"""Stream records: ``first_ack - received``, the program's own time to the
first token, from the proxy's receive to its first write done: free of the
generator's lateness and of the client's connect, send and read.
Nearest-rank p90 over the window's requests that succeeded."""

from benchmarks.metrics import _stream


def read(ctx):
    return _stream.percentile_ms(
        ctx, _stream.between("first_ack", "received"), 90)
