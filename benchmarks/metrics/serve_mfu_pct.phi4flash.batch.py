"""The whole step's share for the Phi-4-flash cells: model operations of the
tokens credited in the window (attention by layer kind, the cross-decoder
for decode tokens and a prompt's last position only:
benchmarks/phi4flash_counts.py) over the window x the chip's published bf16
peak."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.serve_mfu_pct(ctx)
