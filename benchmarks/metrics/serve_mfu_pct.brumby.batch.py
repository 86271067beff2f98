"""The whole step's share for the Brumby cells: model operations of the
tokens credited in the window (2 x the matrices a token meets, the head for
a prompt's last position and every decode token, the retention's
recurrent-form count over the published 8,256 monomials:
benchmarks/brumby_counts.py) over the window x the chip's published bf16
peak."""

from benchmarks import brumby_counts


def read(ctx):
    return brumby_counts.serve_mfu_pct(ctx)
