"""Useful attention operations of the traced prefill chunks (the live
(query, key) pairs of every layer by its kind, from the launch's ``prefix``
and ``tokens``) over (device time of the ``chunk_attn_*`` kernels) x the
chip's bf16 peak."""

from benchmarks import mimo_counts


def read(ctx):
    return mimo_counts.chunk_attn_roofline_pct(ctx)
