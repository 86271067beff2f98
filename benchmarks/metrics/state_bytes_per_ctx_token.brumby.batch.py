"""The seated slots' retention state (row key ``state_bytes``) over the
tokens those slots hold (``kv_tokens``): mean over the window's step-log
rows. The model has no pages; 32,768 B a token is what 8 layers of keys and
values of the same heads would cost."""

from benchmarks import brumby_counts


def read(ctx):
    return brumby_counts.state_bytes_per_ctx_token(ctx)
