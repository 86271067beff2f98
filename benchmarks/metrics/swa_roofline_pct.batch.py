"""Useful window-kind cache bytes of the traced decode steps
(``window_tokens`` x 5,120 B x the 8 window layers) over (device time under
the scopes ``window_gather`` + ``window_attn``) x the chip's HBM peak."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.swa_roofline_pct(ctx)
