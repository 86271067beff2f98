"""``chunk_ahead_share.batch`` (PR 53) on step-log rows made by hand, and what
a trace of a program that sends chunks ahead gives the readers that pair a
device run with its ``engine:launch`` (``benchmarks/progtrace.py``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import progtrace  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

METRICS = os.path.join(ROOT, "benchmarks", "metrics")
NAME = "chunk_ahead_share.batch"
T0 = 1_790_000_000.0   # a wall clock
BATCH_CELLS = [
    "internlm2-1.8b.docs_batch", "deepseek-v2.longdocs_batch",
    "mimo-v2.5.mixed_lengths_batch", "phi-4-mini-flash.reasoning_batch",
    "command-a-plus.grounded_docs_batch", "brumby-14b.long_context_batch"]


def _launch(program, **attrs):
    return {"name": "launch", "t0": 0.0, "t1": 0.0, "program": program,
            **attrs}


def _row(i, slices):
    return {"t0": T0 + i, "t1": T0 + i + 0.1, "active": 1,
            "phases": [{"phase": "decode", "t0": T0 + i, "t1": T0 + i + 0.1}],
            "slices": [{"name": "reap", "t0": 0.0, "t1": 0.0}] + slices}


def _read(rows):
    ctx = {"rows": rows, "wall_window": (T0, T0 + 51.0)}
    return bench_run.read_metric(METRICS, NAME, ctx)


CHUNK = dict(tokens=2048, prefix=4096)
CASES = {
    # three of four chunks went ahead; a decode's and a wave's launch and a
    # chunk outside the window are not counted
    "mixed": ([
        _row(0, [_launch("prefill_chunk", **CHUNK), _launch("decode"),
                 _launch("prefill_chunk", ahead=1, **CHUNK)]),
        _row(1, [_launch("paged_prefill", tokens=300), _launch("decode"),
                 _launch("prefill_chunk", ahead=1, **CHUNK)]),
        _row(2, [_launch("decode"),
                 _launch("prefill_chunk", ahead=1, **CHUNK)]),
        _row(60, [_launch("prefill_chunk", **CHUNK)])], 0.75),
    # a program that sends none ahead (the parent): a reading, and it is 0
    "none_ahead": ([
        _row(0, [_launch("prefill_chunk", **CHUNK), _launch("decode")]),
        _row(1, [_launch("prefill_chunk", **CHUNK), _launch("decode")])],
        0.0),
    "all_ahead": ([
        _row(0, [_launch("decode"),
                 _launch("prefill_chunk", ahead=1, **CHUNK)])], 1.0),
    # whole-prefill waves and decodes only: nothing to read
    "no_chunk": ([
        _row(0, [_launch("paged_prefill", tokens=300), _launch("decode")]),
        _row(1, [_launch("decode")])], None),
    "no_rows": ([], None),
    # rows of a program from before the slices
    "no_slices": ([{"t0": T0 + 1, "t1": T0 + 1.1, "active": 1,
                    "phases": []}], None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_share_on_rows_made_by_hand(case):
    rows, want = CASES[case]
    got = _read(rows)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_benchmark_lists_it_for_the_six_batch_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "share", "better": "higher",
        "source": "program_span", "layer": "admission, batching, KV pages",
        "moves": "serve_tokens_per_s", "workloads": BATCH_CELLS}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(BATCH_CELLS) <= cells
    assert os.path.exists(os.path.join(METRICS, NAME + ".py"))


# ----------------------------------------- a trace with chunks sent ahead


def _host(name, start, dur, **stats):
    return ["engine:" + name, start * 1e6, dur * 1e6, stats]


def _trace(ahead_inside_fetch):
    """Two steps of a steady engine, times in ms: chunk k+1 is launched
    behind decode k, runs on the device once decode k has ended, and ends
    under decode k+1's fetch. ``ahead_inside_fetch``: the decode's
    ``engine:fetch`` opens round the chunk's ``engine:launch``, as the
    program writes it (``StepTimeline.enclose``); else the two tile, and
    the chunk's bracket closes with the fetch of the decode BEFORE it."""
    host, programs = [], []
    for k, t in enumerate((0.0, 100.0, 200.0)):
        # device: chunk k runs t .. t+70 (launched a step earlier), decode
        # k t+70 .. t+95; the host launches decode k at t+12 and chunk k+1
        # at t+14, and its fetch returns at t+95.
        programs += [["jit_engine_paged_suffix", (t + 0.5) * 1e6, 69e6],
                     ["jit_engine_decode", (t + 70) * 1e6, 25e6]]
        host += [_host("sample_emit", t - 5 + 0.2, 8), _host("admit", t + 3.2, 6),
                 _host("pages", t + 9.2, 2.8),
                 _host("launch", t + 12, 2, program="decode", batch=24)]
        chunk = _host("launch", t + 14, 4, program="prefill_chunk",
                      tokens=2048, prefix=2048 * (k + 1), ahead=1)
        if ahead_inside_fetch:
            host += [_host("fetch", t + 13.99, 81.2, program="decode"), chunk]
        else:
            host += [chunk, _host("fetch", t + 18, 77.2, program="decode")]
    host.sort(key=lambda e: e[1])
    return {"host": host, "programs": programs, "ops": []}


def _paired_prefixes(trace):
    runs = [{"program": n, "t0": s, "t1": s + d, "ops": []}
            for n, s, d in trace["programs"]]
    pairs = progtrace.pair(runs, progtrace.launches(trace))
    return [None if ln is None else ln["stats"].get("prefix")
            for run, ln in zip(runs, pairs)
            if run["program"] == "jit_engine_paged_suffix"], pairs


def test_a_chunk_sent_ahead_pairs_with_its_launch_under_the_next_fetch():
    got, pairs = _paired_prefixes(_trace(ahead_inside_fetch=True))
    # The first run's launch lies before the trace; each later chunk finds
    # the launch that sent it, a step earlier.
    assert got == [None, 2048, 4096]
    roles = [ln["role"] for ln in pairs if ln is not None]
    assert roles.count("decode") == 3 and roles.count("prefill_chunk") == 2


def test_tiled_annotations_would_lose_every_chunk_sent_ahead():
    """Why the program nests them: were the decode's fetch to open BEHIND
    the chunk's launch, the chunk's bracket would close before its run on
    the device began, and no reader would find the run."""
    got, _ = _paired_prefixes(_trace(ahead_inside_fetch=False))
    assert got == [None, None, None]
