"""Pool bytes of the pages in use of both kinds (row keys ``pages_full``,
``pages_window``) plus the seated slots' recurrent state (``state_bytes``),
over the tokens those slots hold (``kv_tokens``): mean over the window's
step-log rows."""

from benchmarks import phi4flash_counts


def read(ctx):
    return phi4flash_counts.kv_bytes_per_ctx_token(ctx)
