"""Live (query, key) pairs of the traced prefill chunks by layer kind (from
the launch's ``prefix`` and ``tokens``) x 4 x 128 heads x 128 operations
over the ``chunk_attn_*`` kernels' device time x the bf16 peak."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.chunk_attn_roofline_pct(ctx)
