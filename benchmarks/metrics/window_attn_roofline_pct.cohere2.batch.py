"""Useful cache bytes of the traced decode steps' window layers
(``window_tokens`` x 12,288 B) over the device time under ``window_attn`` x
the HBM peak."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.decode_attn_roofline_pct(ctx, window=True)
