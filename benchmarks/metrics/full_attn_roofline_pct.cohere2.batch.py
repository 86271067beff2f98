"""Useful cache bytes of the traced decode steps' full layer (``ctx_tokens``
x 4,096 B) over the device time under ``full_attn`` x the HBM peak."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.decode_attn_roofline_pct(ctx, window=False)
