"""The cases of ``tests/test_chunk_ahead.py`` (PR 53) that hold for every
model behind the engine's seam, as functions of ``make(**engine arguments)
-> (engine, config)``: each model's own test file runs them on its model
(``test_chunk_ahead.py`` llama's debug preset, ``test_page_kinds.py`` a
model with page kinds, ``test_slot_state.py`` one with slot state,
``test_no_page_kind.py`` the one with no page kind), so that a model's
programs compile in one test process, as before."""

import time

import numpy as np


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def run(eng, reqs, steps=3000):
    for _ in range(steps):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError(f"not done: {[r.status for r in reqs]}")


def stand_back(eng):
    """The ahead tick never finds its pages: every chunk runs at the
    tick's usual place, as on the parent."""
    eng._free_lists_cover = lambda slot, tokens: False


def nothing_leaked(eng):
    assert sorted(eng._free) == list(range(eng.slots))
    assert not eng._active and not eng._prefilling
    assert eng._ahead is None or eng._ahead[1] is None  # no ids are held
    if eng._kind is not None:
        assert eng._pages.in_use == 0
        assert not eng._block_tables.any()
    for w in eng._windows.values():
        assert w.alloc.in_use == 0 and not w.table.any()


def launches(eng, program="prefill_chunk"):
    return [s for r in eng.steplog.dump()["rows"] for s in r["slices"]
            if s["name"] == "launch" and s.get("program") == program]


def greedy_streams_are_those_of_an_engine_that_stands_back(make):
    """Five prompts through three slots, three of them longer than a
    chunk, the last two waiting for a slot: admission waves, chunks at the
    tick's usual place, chunks ahead and their endings all meet."""
    out, engines = [], []
    for back in (False, True):
        eng, cfg = make()
        if back:
            stand_back(eng)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(
            prompts(cfg, [9, 100, 70, 20, 45], seed=3), [12, 4, 9, 6, 5])]
        run(eng, reqs)
        assert [r.status for r in reqs] == ["completed"] * 5
        out.append([r.output for r in reqs])
        nothing_leaked(eng)
        engines.append(eng)
        eng.shutdown()
    went, stood = engines
    assert out[0] == out[1]
    assert went.prefill_chunks == stood.prefill_chunks >= 4 + 3 + 2
    assert stood.prefill_chunks_ahead == 0
    # Only a prompt's first chunk, and a chunk that found no decode to go
    # behind, ran at the tick's usual place.
    assert went.prefill_chunks_ahead >= went.prefill_chunks - 4
    assert went.stats()["prefill_chunks_ahead"] == went.prefill_chunks_ahead
    ahead = [s.get("ahead") for s in launches(went)]
    assert ahead.count(1) == went.prefill_chunks_ahead
    assert not any(s.get("ahead") for s in launches(stood))


def no_two_chunks_lie_between_two_decodes(make):
    """By the order of dispatches, which is the order the device runs them
    in: two chunks follow each other only where no slot was active behind
    the first to be starved (no decode was there to dispatch). A host step
    dispatches at most two chunks, its own and the next step's."""
    eng, cfg = make()
    order = []
    dispatch = eng._dispatch_fresh

    def spy(key, call, then=None, **attrs):
        order.append((attrs.get("program", key[0]), len(eng._active),
                      attrs.get("ahead", 0)))
        return dispatch(key, call, then, **attrs)

    eng._dispatch_fresh = spy
    texts = prompts(cfg, [100, 9, 90, 70], seed=7)
    reqs = [eng.submit(texts[0], max_new_tokens=3)]
    eng.step()
    eng.step()      # two chunks of a prompt that prefills alone
    reqs += [eng.submit(p, max_new_tokens=n)
             for p, n in zip(texts[1:], [30, 5, 4])]
    for _ in range(3000):
        before = eng.prefill_chunks
        eng.step()
        assert eng.prefill_chunks <= before + 2
        if all(r.done.is_set() for r in reqs):
            break
    assert all(r.status == "completed" for r in reqs)
    chunks = [i for i, (p, _, _) in enumerate(order) if p == "prefill_chunk"]
    assert len(chunks) >= 4 + 3 + 3
    back_to_back = 0
    for a, b in zip(chunks, chunks[1:]):
        if not any(p == "decode" for p, _, _ in order[a + 1:b]):
            back_to_back += 1
            # Whoever is active at b was seated since a, by an admission
            # wave that emitted its first token.
            assert order[a + 1][1] == 0, order[a:b + 1]
    # A chunk sent ahead lies right behind a decode.
    for i in chunks:
        if order[i][2]:
            assert order[i - 1][0] == "decode"
    assert any(order[i][2] for i in chunks)
    # The first prompt prefilled alone: no slot was active, nothing to go
    # behind, and its chunks followed each other as they do on the parent.
    assert back_to_back >= 1
    eng.shutdown()


def with_a_last_chunk_in_flight(make):
    """An engine one of whose requests decodes while the LAST chunk of
    another's prompt has been sent ahead: its ids are on the device,
    nobody has fetched them."""
    eng, cfg = make()
    short, long_ = prompts(cfg, [9, 80], seed=11)
    first = eng.submit(short, max_new_tokens=60)
    eng.step()
    second = eng.submit(long_, max_new_tokens=5)
    for _ in range(20):
        eng.step()
        if eng._ahead is not None and eng._ahead[1] is not None:
            break
    slot, ids = eng._ahead
    assert eng._prefilling[slot] is second and second.generated == 0
    assert second.prefilled == len(second.tokens)
    fetched = []
    fetch = eng._fetch_ids

    def spy(got, program):
        fetched.append(got)
        return fetch(got, program)

    eng._fetch_ids = spy
    return eng, first, second, ids, fetched


def a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(make, how):
    eng, first, second, ids, fetched = with_a_last_chunk_in_flight(make)
    slot = second.slot
    if how == "cancel":
        assert eng.cancel(second.request_id)
    elif how == "deadline":
        second.deadline = time.monotonic() - 1.0
    elif how == "preempt":
        assert eng._preempt_one()       # the youngest: the one prefilling
        assert second.slot == -1 and second.prefilled == 0
        # The record goes with the slot; that a chunk went ahead stays, so
        # the next tick still dispatches none in front of the decode.
        assert eng._ahead == (slot, None)
    if how == "shutdown":
        eng.shutdown()
        assert first.status == second.status == "cancelled"
    else:
        chunks = eng.prefill_chunks
        eng.step()
        # The step met the chunk that went ahead: it sent none of its own
        # in front of its decode, at most the next one behind it.
        ran = launches(eng)[chunks:]
        assert all(s.get("ahead") for s in ran) and len(ran) <= 1
        run(eng, [first, second])
    assert second.status == {
        "cancel": "cancelled", "deadline": "deadline_exceeded",
        "preempt": "completed", "shutdown": "cancelled"}[how]
    assert all(got is not ids for got in fetched)
    nothing_leaked(eng)
    if how == "preempt":
        # The stream is the one an engine gives that never preempted.
        assert eng.preempted == 1 and len(second.output) == 5
        alone, _ = make()
        again = alone.submit(second.tokens[:second.prompt_len].tolist(),
                             max_new_tokens=5)
        run(alone, [again])
        assert again.output == second.output
        alone.shutdown()
    eng.shutdown()


def a_dry_free_list_stands_the_ahead_tick_back(make, alloc_of):
    """It asks for what is free and nothing else: with every free page of
    one kind taken, no chunk goes ahead, nobody is preempted for it, and
    the chunk runs at the tick's usual place once pages are back."""
    eng, cfg = make()
    short, long_ = prompts(cfg, [9, 120], seed=13)
    first = eng.submit(short, max_new_tokens=40)
    eng.step()
    second = eng.submit(long_, max_new_tokens=3)
    eng.step()                      # its first chunk, and its second ahead
    assert (eng.prefill_chunks, eng.prefill_chunks_ahead) == (2, 1)
    eng.step()                      # the third ahead
    assert (eng.prefill_chunks, eng.prefill_chunks_ahead) == (3, 2)
    alloc = alloc_of(eng)
    # All but a page the decoding slot may need; the chunk needs several.
    held = alloc.alloc(alloc.free_count - 1)
    eng.step()
    assert (eng.prefill_chunks, eng.prefill_chunks_ahead) == (3, 2)
    assert eng.preempted == 0 and eng._ahead is None
    assert second.prefilled == 96
    alloc.free(held)
    eng.step()                      # at the tick's usual place
    assert (eng.prefill_chunks, eng.prefill_chunks_ahead) == (4, 2)
    last = launches(eng)[-1]
    assert "ahead" not in last and last["tokens"] == 24
    run(eng, [first, second])
    assert eng.preempted == 0
    nothing_leaked(eng)
    eng.shutdown()
