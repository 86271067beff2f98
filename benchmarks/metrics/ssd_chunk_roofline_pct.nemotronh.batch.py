"""The traced prefills' Mamba-2 layers: the chunked form's matmuls at the
published sub-chunk of 128 (6,553,600 a token a layer) of the launch's
``tokens`` over (device time under the scope ``ssd_chunk``) x the chip's
bf16 peak."""

from benchmarks import nemotron_h_counts


def read(ctx):
    return nemotron_h_counts.ssd_chunk_roofline_pct(ctx)
