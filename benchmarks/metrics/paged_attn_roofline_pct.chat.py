"""Useful KV bytes of the traced decode steps over (device time under the
scopes ``paged_gather`` + ``paged_attn``) x the chip's HBM peak."""

from benchmarks import kernel_counts


def read(ctx):
    return kernel_counts.paged_attn_roofline_pct(ctx)
