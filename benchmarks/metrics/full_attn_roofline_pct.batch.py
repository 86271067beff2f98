"""Useful full-kind cache bytes of the traced decode steps (``ctx_tokens`` x
2,560 B x the full layers) over (device time under the scopes
``full_gather`` + ``full_attn``) x the chip's HBM peak."""

from benchmarks import mimo_counts


def read(ctx):
    return mimo_counts.decode_attn_roofline_pct(ctx, window=False)
