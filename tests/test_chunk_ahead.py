"""PR 53: the next step's prefill chunk is dispatched behind this step's
decode, BEFORE the host waits for the decode's ids (``DecodeEngine.step``,
``_prefill_ahead``; docs/SERVING.md "Chunked-prefill / decode
interleaving"). The device's order of programs is what it was (chunk k,
decode k, chunk k+1, decode k+1); only the host's order changed, so greedy
streams are token for token those of an engine whose ahead tick stands back.
The seam that stands it back here is the check it makes itself,
``_free_lists_cover`` (no configuration key). All CPU, debug presets:
tier-1.

The cases that hold for every model are in ``tests/chunk_ahead_cases.py``;
this file runs them on llama's debug preset and each other model's own test
file on its model (``test_page_kinds.py``, ``test_slot_state.py``,
``test_no_page_kind.py``)."""

import threading

import pytest

import chunk_ahead_cases as cases
from chunk_ahead_cases import (launches as _launches,
                               nothing_leaked as _nothing_leaked,
                               prompts as _prompts, run as _run)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


_BUILT = []


def _engine(name="llama", **kw):
    """An engine over llama's debug preset (``name`` is kept for the
    callers' sake: the other models run the cases in their own files)."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    if not _BUILT:
        cfg = llama.PRESETS["debug"]
        _BUILT.append((cfg, llama.init_params(cfg, jax.random.key(0))))
    cfg, params = _BUILT[0]
    args = dict(slots=3, capacity=128, prefill_chunk_tokens=32,
                page_tokens=16, prefix_pool_entries=0, step_timeline=4096,
                metrics_enabled=False, trace_spans=False)
    args.update(kw)
    return DecodeEngine(params, cfg, **args), cfg


# ------------------------------------------------- the streams are the same


def _make(**kw):
    return _engine("llama", **kw)


def test_greedy_streams_are_those_of_an_engine_that_stands_back():
    cases.greedy_streams_are_those_of_an_engine_that_stands_back(_make)


def test_no_two_chunks_lie_between_two_decodes():
    cases.no_two_chunks_lie_between_two_decodes(_make)


@pytest.mark.parametrize("how", ["cancel", "deadline", "preempt",
                                 "shutdown"])
def test_a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(how):
    cases.a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(
        _make, how)


def test_a_dry_free_list_stands_the_ahead_tick_back():
    cases.a_dry_free_list_stands_the_ahead_tick_back(
        _make, lambda eng: eng._pages)


def _with_a_last_chunk_in_flight(name):
    return cases.with_a_last_chunk_in_flight(_make)



def test_sampled_rows_draw_from_the_seed_and_the_traffic_alone():
    """A row with a temperature draws from a counter of the prefill
    programs dispatched: another than an engine that stands back reads,
    still the same from run to run."""
    runs = []
    for _ in range(2):
        eng, cfg = _engine("llama")
        reqs = [eng.submit(p, max_new_tokens=8, temperature=0.9)
                for p in _prompts(cfg, [9, 100, 70], seed=5)]
        _run(eng, reqs)
        runs.append([r.output for r in reqs])
        assert eng.prefill_chunks_ahead > 0
        eng.shutdown()
    assert runs[0] == runs[1]


# ------------------------------------------------------ the device's order


# ------------------------------------- a request whose chunk is in flight


def test_a_last_chunk_sent_ahead_seats_its_slot_in_the_next_decode():
    eng, first, second, ids, fetched = _with_a_last_chunk_in_flight("llama")
    n = first.generated
    assert eng.step() == 2              # both decoded
    # Its ids were fetched at the tick's usual place, its first token
    # emitted there and its second by the decode it joined.
    assert [got is ids for got in fetched] == [True]
    assert second.generated == 2 and first.generated == n + 1
    assert second.slot in eng._active and not eng._prefilling
    row = eng.steplog.dump()["rows"][-1]
    names = [(s["name"], s.get("program")) for s in row["slices"]]
    at = names.index(("fetch", "prefill_chunk"))
    assert names.index(("launch", "decode")) > at
    assert ("launch", "prefill_chunk") not in names
    _run(eng, [first, second])
    _nothing_leaked(eng)
    eng.shutdown()


def test_the_loop_sees_a_last_chunk_in_flight_as_work():
    """The only work left may be a chunk in flight whose prompt has ended:
    ``serve_forever`` must step once more to fetch its ids."""
    eng, first, second, ids, fetched = _with_a_last_chunk_in_flight("llama")
    eng.cancel(first.request_id)
    assert not eng._pending.qsize() and not eng._requeue
    loop = threading.Thread(target=eng.serve_forever, daemon=True)
    loop.start()
    assert second.done.wait(30.0) and second.status == "completed"
    assert len(second.output) == 5 and first.status == "cancelled"
    eng.shutdown()
    loop.join(10.0)
    _nothing_leaked(eng)


# ------------------------------------------------------- what stands it back


def test_it_reclaims_no_prefix_pin():
    """A short free list with pages the prefix index could give back: the
    tick at its usual place reclaims them, the ahead tick does not."""
    eng, cfg = _engine("llama", prefix_pool_entries=4,
                       prefix_match_min_tokens=16, pool_pages=14)
    warm, short, long_ = _prompts(cfg, [100, 9, 120], seed=17)
    done = eng.submit(warm, max_new_tokens=2)
    _run(eng, [done])
    pinned = eng.prefix.pinned_pages
    assert pinned >= 2 and eng._pages.free_count == 14 - pinned
    # Eight free pages: one for the short prompt, six for three chunks,
    # and one where the fourth chunk (24 tokens) needs two.
    held = eng._pages.alloc(eng._pages.free_count - 8)
    first = eng.submit(short, max_new_tokens=6)
    eng.step()
    second = eng.submit(long_, max_new_tokens=3)
    calls, inside = [], []
    ahead, reclaim = eng._prefill_ahead, eng.prefix.reclaim

    def spy_ahead():
        inside.append(1)
        try:
            ahead()
        finally:
            inside.pop()

    eng._prefill_ahead = spy_ahead
    eng.prefix.reclaim = lambda *a, **kw: (calls.append((bool(inside), kw)),
                                           reclaim(*a, **kw))[1]
    _run(eng, [first, second])
    # Pins were given back for want of free pages (``_alloc_pages``' call
    # names no keyword), and never from inside the ahead tick.
    assert any(not kw for _, kw in calls)
    assert not any(within for within, _ in calls)
    # The prompt's first chunk and the one that stood back for want of a
    # page ran at the tick's usual place, where the pins were reclaimed.
    assert eng.prefill_chunks_ahead >= 1 and eng.preempted == 0
    assert eng.prefill_chunks - eng.prefill_chunks_ahead >= 2
    eng._pages.free(held)
    eng.shutdown()


def test_no_decode_no_chunk_ahead():
    """It goes ahead only behind a dispatched decode: a prompt that
    prefills alone takes a chunk a step, at the tick's usual place."""
    eng, cfg = _engine("llama")
    req = eng.submit(_prompts(cfg, [100], seed=19)[0], max_new_tokens=6)
    steps = 0
    while not req.generated:
        assert eng.step() in (0, 1)
        steps += 1
    assert steps == 4 and eng.prefill_chunks == 4
    assert eng.prefill_chunks_ahead == 0
    _run(eng, [req])
    eng.shutdown()
