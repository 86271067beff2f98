"""The whole step's share for the Command A+ cells: model operations of the
tokens credited in the window (held experts by the decode steps' counted
pairs and a prompt's by expectation, attention's live pairs by kind:
benchmarks/cohere2_moe_counts.py) over the window x the chip's published
bf16 peak; nothing off the chip."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.serve_mfu_pct(ctx)
