"""Useful bytes of the ONE cache in the traced decode steps (``ctx_tokens``
x 5,120 B x the 8 layers that read it) over (device time under the scopes
``full_gather`` + ``full_attn`` + ``cross_attn``) x the chip's HBM peak: the
same work whether the pages are gathered once a step or once a layer."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.shared_kv_roofline_pct(ctx)
