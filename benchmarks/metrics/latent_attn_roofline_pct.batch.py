"""Useful latent-cache bytes of the traced decode steps (``ctx_tokens`` x 576
x 2 B x layers) over (device time under the scopes ``latent_gather`` +
``latent_attn``) x the chip's HBM peak."""

from benchmarks import deepseek_counts


def read(ctx):
    return deepseek_counts.latent_attn_roofline_pct(ctx)
