"""ONE stale assertion, held open until a ``benchmark`` PR repairs it.

``test_progtrace.py::test_every_new_metric_has_its_reader_and_its_cells``
(PR 24) ends on ``bench["per_layer"][-16:] == PR 24's sixteen``: true only
while no later PR appends a per-layer metric, and the contract puts new
entries at the END of their lists. PR 36 appends five, and may not edit a
file the benchmark already has. The test is NOT switched off: it runs, and
every assertion before the last one fails it as before (a reader file for
each of the sixteen, their ``workloads`` within those of the end-to-end
metric each moves, roofline = the kernels layer). Only a failure raised BY
THAT LAST LINE, found by its source text, is reported as an expected
failure. Once the line is repaired (compare the block's position, not the
list's tail) nothing here applies and this file can go (ROADMAP B17);
``test_deepseek_cell.py::test_earlier_metrics_keep_their_place`` holds
what the line meant meanwhile."""

import linecache

import pytest

STALE = ("test_progtrace.py::"
         "test_every_new_metric_has_its_reader_and_its_cells")
STALE_LINE = 'bench["per_layer"][-16:]'


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    outcome = yield
    if not item.nodeid.endswith(STALE) or outcome.excinfo is None:
        return
    kind, _, tb = outcome.excinfo
    while tb.tb_next is not None:
        tb = tb.tb_next
    line = linecache.getline(tb.tb_frame.f_code.co_filename, tb.tb_lineno)
    if kind is AssertionError and STALE_LINE in line:
        try:
            pytest.xfail("only the tail assertion failed: per-layer metrics "
                         "were appended after PR 24's (conftest.py)")
        except pytest.xfail.Exception:
            import sys

            outcome.force_exception(sys.exc_info()[1])
