"""An engine over a model with NO page kind (``DecodeEngine`` with
``_kind`` None; docs/SERVING.md, "A model with no page kind"): the
model's ``page_kinds`` is empty and every leaf of its pool is slot state, so
the engine builds no allocator, no block table and no view, runs ONE decode
program, seats by slots alone and never preempts. Driven at the debug preset
of Brumby (``models/brumby.py``: three power-retention layers), on the
CPU."""

import numpy as np
import pytest

import chunk_ahead_cases as cases

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import brumby

    cfg = brumby.PRESETS["debug"]
    return cfg, brumby.init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    from ray_tpu.models import brumby_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    args = dict(slots=3, capacity=128, page_tokens=8,
                prefill_chunk_tokens=32, model=brumby_decode,
                step_timeline=4096, metrics_enabled=False,
                trace_spans=False)
    args.update(kw)
    return DecodeEngine(params, cfg, **args)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def _run(eng, reqs, steps=3000, each=None):
    for _ in range(steps):
        eng.step()
        if each is not None:
            each()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


def _events(eng):
    return [e["kind"] for r in eng.steplog.dump()["rows"]
            for e in r.get("events", [])]


def test_the_engine_builds_no_allocator_no_table_and_no_view(model):
    eng = _engine(model)
    assert eng._kind is None and eng._windows == {}
    assert eng._pages is None and eng._block_tables is None
    assert eng._slot_pages is None and eng.prefix is None
    assert eng.pool_pages == 0 and eng._view_ladder == (0,)
    assert eng._state_leaves == ("S", "z")
    assert set(eng.cache) == {"S", "z", "length"}
    assert eng.pages_in_use() == {}
    # The model has no ``live_page_view`` to call.
    assert not hasattr(eng._ld, "live_page_view")
    s = eng.stats()
    assert (s["pages_total"], s["pages_free"], s["pages_in_use"],
            s["pages_pinned"], s["kv_fragmentation"]) == (0, 0, 0, 0, 0.0)
    cfg = model[0]
    per_slot = cfg.n_layers * cfg.n_kv_heads * (cfg.head_dim + 1) \
        * cfg.state_rows * 4
    assert s["state_bytes_total"] == 3 * per_slot
    eng.shutdown()


def test_a_model_with_neither_pages_nor_state_is_refused(model):
    from ray_tpu.models import brumby_decode
    from ray_tpu.serve.decode import DecodeEngine

    class Nothing:
        __name__ = "nothing"

        def __getattr__(self, name):
            if name == "slot_state":
                raise AttributeError(name)
            return getattr(brumby_decode, name)

    cfg, params = model
    with pytest.raises(ValueError, match="cache nothing"):
        DecodeEngine(params, cfg, slots=2, capacity=64, page_tokens=8,
                     model=Nothing())


def test_slots_alone_bind_admission_and_nothing_is_preempted(model):
    """Seven requests over three slots, whole prefills and chunked ones:
    at most three are seated at any step, all finish, no page event is
    logged, nothing is preempted, and the decode is ONE program."""
    eng = _engine(model)
    cfg = model[0]
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(cfg, (9, 70, 33, 20, 100, 12, 41))]
    seated = []
    _run(eng, reqs, each=lambda: seated.append(
        len(eng._active) + len(eng._prefilling)))
    assert max(seated) == 3
    assert all(len(r.output) == 6 and r.status == "completed" for r in reqs)
    kinds = set(_events(eng))
    assert not kinds & {"page-alloc", "page-free", "preempt"}, kinds
    assert eng.stats()["preempted"] == 0 and eng.preempted == 0
    decodes = [k for k in eng._compiled if k[0] == "decode"]
    assert decodes == [("decode", 0)]
    # A chunk's program is keyed by its bucket alone: no table, no width.
    assert {k[3] for k in eng._compiled if k[0] == "paged_suffix"} == {0}
    assert sorted(eng._free) == [0, 1, 2]
    eng.shutdown()


def test_a_request_past_capacity_is_refused_and_one_inside_it_is_not(model):
    eng = _engine(model, capacity=64)
    cfg = model[0]
    with pytest.raises(ValueError, match="exceeds the cache capacity"):
        eng.submit(_prompts(cfg, (60,))[0], max_new_tokens=8)
    with pytest.raises(ValueError, match="shorter than the cache capacity"):
        eng.submit(_prompts(cfg, (64,))[0], max_new_tokens=1)
    # What a paged engine of no pages would refuse for want of pages.
    req = eng.submit(_prompts(cfg, (56,))[0], max_new_tokens=8)
    _run(eng, [req])
    assert len(req.output) == 8
    eng.shutdown()


@pytest.mark.parametrize("how", ["prefill_only", "adopt"])
def test_a_handoff_is_refused_where_it_is_asked_for(model, how):
    eng = _engine(model)
    kw = ({"prefill_only": True} if how == "prefill_only"
          else {"adopt": {"committed_len": 4}})
    with pytest.raises(ValueError, match="keeps slot state"):
        eng.submit([1, 2, 3, 4], max_new_tokens=2, **kw)
    eng.shutdown()


def test_a_mesh_whose_program_the_model_lacks_is_refused(model):
    with pytest.raises(ValueError, match="shard_decode_state"):
        _engine(model, mesh_shape=(1, 2))


def test_the_rows_and_the_launches_say_no_pages_and_carry_the_state(model):
    eng = _engine(model)
    cfg = model[0]
    reqs = [eng.submit(p, max_new_tokens=5)
            for p in _prompts(cfg, (40, 17))]
    _run(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    per_slot = eng._slot_state_bytes
    busy = [r for r in rows if r.get("kv_tokens")]
    assert busy and all(r["pages_free"] == 0 for r in rows)
    assert not [k for r in rows for k in r if k.startswith("pages_")
                and k not in ("pages_free", "pages_pinned")]
    assert {r["state_bytes"] for r in busy} <= {per_slot, 2 * per_slot}
    launches = [s for r in rows for s in r["slices"]
                if s["name"] == "launch"]
    decodes = [s for s in launches if s["program"] == "decode"]
    assert decodes and all(
        s["view_pages"] == 0 and s["live_pages"] == 0
        and s["state_slots"] == s["batch"] for s in decodes)
    chunks = [s for s in launches if s["program"] == "prefill_chunk"]
    assert [(s["prefix"], s["tokens"]) for s in chunks] == [(0, 32), (32, 8)]
    assert [s["cross_rows"] for s in chunks] == [0, 1]
    eng.shutdown()


def test_a_reused_slot_starts_from_zero_and_serves_what_a_fresh_engine_does(
        model):
    """One slot, two requests one after the other: the second finds the
    first's state in its slot and must not read it."""
    cfg = model[0]
    first, second = _prompts(cfg, (50, 23), seed=3)
    eng = _engine(model, slots=1)
    a = eng.submit(first, max_new_tokens=5)
    _run(eng, [a])
    assert float(np.abs(np.asarray(eng.cache["S"][:, 0])).max()) > 0
    b = eng.submit(second, max_new_tokens=7)
    _run(eng, [b])
    eng.shutdown()
    fresh = _engine(model, slots=1)
    c = fresh.submit(second, max_new_tokens=7)
    _run(fresh, [c])
    fresh.shutdown()
    assert b.output == c.output


def test_a_decode_step_between_two_chunks_leaves_the_state_bit_for_bit(
        model):
    """A slot mid-prefill is outside the decode's batch: a step that runs
    none of its chunks leaves its state as the last chunk left it, while
    the other slot decodes."""
    eng = _engine(model, slots=2)
    cfg = model[0]
    short, long_ = _prompts(cfg, (10, 90), seed=5)
    a = eng.submit(short, max_new_tokens=40)
    _run(eng, [], steps=2)          # seated and decoding
    b = eng.submit(long_, max_new_tokens=3)
    # One chunk a step is the engine's rule; hold every other one back so
    # that decode-only steps fall between two chunks of b's.
    eng._prefill_ahead = lambda: None   # none behind a decode either
    tick, held = eng._prefill_tick, []
    eng._prefill_tick = lambda: held.append(1) if len(held) % 2 == 0 \
        else (held.append(1), tick())
    compared = 0
    for _ in range(200):
        mid = b.slot in eng._prefilling and b.prefilled > 0
        before = np.asarray(eng.cache["S"][:, b.slot]) if mid else None
        chunks = eng.prefill_chunks
        eng.step()
        if mid and eng.prefill_chunks == chunks:
            np.testing.assert_array_equal(
                before, np.asarray(eng.cache["S"][:, b.slot]))
            compared += 1
        if a.done.is_set() and b.done.is_set():
            break
    assert a.done.is_set() and b.done.is_set() and compared >= 2
    eng.shutdown()
    # What b served is what it serves alone.
    alone = _engine(model, slots=1)
    c = alone.submit(long_, max_new_tokens=3)
    _run(alone, [c])
    alone.shutdown()
    assert b.output == c.output


def test_the_deployment_serves_it_through_the_one_engine():
    """``BrumbyDecodeDeployment`` is the dozen lines the other four are:
    built bare (no runtime), it serves a request and reports the page
    health keys the controller reads, all zero."""
    from ray_tpu.serve.decode import (BrumbyDecodeDeployment,
                                      LlamaDecodeDeployment)

    assert issubclass(BrumbyDecodeDeployment, LlamaDecodeDeployment)
    dep = BrumbyDecodeDeployment(preset="debug", slots=2, capacity=64,
                                 kv_page_tokens=8, prefill_chunk_tokens=16)
    try:
        out = dep({"tokens": [5, 9, 2, 7] * 6, "max_new_tokens": 4})
        assert len(out["tokens"]) == 4
        m = dep.replica_metrics()
        assert (m["pages_total"], m["pages_free"], m["pages_in_use"],
                m["preempted"]) == (0, 0, 0, 0)
        assert dep.engine._kind is None
    finally:
        dep.engine.shutdown()


def test_greedy_streams_are_those_of_an_engine_that_stands_back(model):
    """A chunk sent ahead of the fetch (PR 53) with nothing to allocate:
    tests/chunk_ahead_cases.py on the model with no page kind (its
    ``_free_lists_cover`` is always true; the seam stands it back)."""
    cases.greedy_streams_are_those_of_an_engine_that_stands_back(
        lambda **kw: (_engine(model, **kw), model[0]))
