"""The whole step's share for the MiMo-V2 cells: model operations of the
tokens credited in the window (held experts only, attention by kind:
benchmarks/mimo_counts.py) over the window x the chip's published bf16
peak; nothing off the chip."""

from benchmarks import mimo_counts


def read(ctx):
    return mimo_counts.serve_mfu_pct(ctx)
