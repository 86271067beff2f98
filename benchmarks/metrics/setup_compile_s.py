"""Set-up record: seconds inside the compiler's own timer before the window
(``compile_watch``'s ``compile_s``, cache retrieval included). A replica:
what ``ready`` had counted plus the later ``first_dispatch`` records'. A
trainer: the worker's snapshot at the window's close (``compiles_in_window
.train`` is 0). A VIEW of seconds the tiling phases already hold."""

from benchmarks.metrics import _setup


def read(ctx):
    return _setup.total(ctx, "compile_s")
