"""The seated slots' Mamba-2 state (row key ``state_bytes``) plus the pool
bytes of the pages in use (``pages_full`` x 64 tokens x 1,024 B) over the
tokens those slots hold (``kv_tokens``): mean over the window's step-log
rows. Five attention layers in the Mamba-2 layers' place would cost 6,144 B
a token at any length."""

from benchmarks import nemotron_h_counts


def read(ctx):
    return nemotron_h_counts.cache_bytes_per_ctx_token(ctx)
