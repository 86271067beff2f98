"""Pool bytes of the pages in use of both kinds (row keys ``pages_full``,
``pages_window``) over the tokens the seated slots hold (``kv_tokens``):
mean over the window's step-log rows. One kind of page reads 16,384."""

from benchmarks import cohere2_moe_counts


def read(ctx):
    return cohere2_moe_counts.kv_bytes_per_ctx_token(ctx)
