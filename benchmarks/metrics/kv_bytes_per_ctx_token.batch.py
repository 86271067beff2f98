"""Pool bytes of the pages in use, both kinds (row keys ``pages_full``,
``pages_window``), over the tokens the seated slots hold (``kv_tokens``):
mean over the window's step-log rows."""

from benchmarks import mimo_counts


def read(ctx):
    return mimo_counts.kv_bytes_per_ctx_token(ctx)
