"""Set-up record: of the compile requests before the window
(``setup_compile_s``'s records), the share the persistent cache answered.
1.0 = every program was loaded, none built."""

from benchmarks.metrics import _setup


def read(ctx):
    hits, asked = (_setup.total(ctx, "cache_hits"),
                   _setup.total(ctx, "compiles"))
    return hits / asked if asked else None
