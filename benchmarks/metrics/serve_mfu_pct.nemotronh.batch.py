"""The whole step's share for the Nemotron-H cells: model operations of the
tokens credited in the window (2 x the matrices a token meets, the held
experts' pairs by expectation, the Mamba-2 layers by the recurrence,
attention's live pairs, the head for a prompt's last position and every
decode token: benchmarks/nemotron_h_counts.py) over the window x the chip's
published bf16 peak."""

from benchmarks import nemotron_h_counts


def read(ctx):
    return nemotron_h_counts.serve_mfu_pct(ctx)
