"""Useful window-kind cache bytes of the traced decode steps
(``window_tokens`` x 5,120 B x the window layers) over (device time under
the scopes ``window_gather`` + ``window_attn``) x the chip's HBM peak."""

from benchmarks import mimo_counts


def read(ctx):
    return mimo_counts.decode_attn_roofline_pct(ctx, window=True)
