"""The traced prefills' power retention: the recurrent form's operations of
the launch's ``tokens`` (2 x 8,256 x 128 x (8 + 40) a token a layer) over
(device time under the scope ``retention_chunk``) x the chip's bf16 peak."""

from benchmarks import brumby_counts


def read(ctx):
    return brumby_counts.retention_chunk_roofline_pct(ctx)
