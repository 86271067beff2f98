"""The whole step's share: model operations of the tokens credited in the
window (held experts only, benchmarks/deepseek_counts.py) over the window x
the chip's published bf16 peak; nothing off the chip."""

from benchmarks import deepseek_counts


def read(ctx):
    return deepseek_counts.serve_mfu_pct(ctx)
