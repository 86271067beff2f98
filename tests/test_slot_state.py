"""Slot state in the one manager (``DecodeEngine``, "Slot state";
docs/SERVING.md): a model may keep leaves of its pool a SLOT and not a page
(a recurrent layer's state). Driven at the debug preset of Phi-4-flash
(``models/phi4flash.py``: two Mamba-window pairs, the middle two layers, one
GMU-cross pair; window 12), on the CPU."""

import numpy as np
import pytest

import chunk_ahead_cases as cases

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

STATE = ("ssm", "conv")


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import phi4flash

    cfg = phi4flash.PRESETS["debug"]
    return cfg, phi4flash.init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    from ray_tpu.models import phi4flash_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    args = dict(slots=4, capacity=256, page_tokens=4,
                prefill_chunk_tokens=32, model=phi4flash_decode,
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


def _state(eng, slot):
    return {k: np.asarray(eng.cache[k][:, slot]) for k in STATE}


def _serve(model, prompts, new, **kw):
    eng = _engine(model, **kw)
    reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    _run(eng, reqs)
    eng.shutdown()
    return [r.output for r in reqs]


def test_the_engine_knows_its_state_leaves_and_runs_without_an_index(model):
    eng = _engine(model)
    cfg = model[0]
    assert eng._state_leaves == STATE and eng.prefix is None
    assert list(eng._windows) == ["window"]
    per_slot = 3 * (cfg.d_state * cfg.d_inner * 4 + 3 * cfg.d_inner * 4)
    assert eng._slot_state_bytes == per_slot
    assert eng.stats()["state_bytes_total"] == 4 * per_slot
    # One row a slot and the scratch row behind them.
    assert all(eng.cache[k].shape[1] == 5 for k in STATE)
    eng.shutdown()


@pytest.mark.parametrize("chunked", [False, True])
def test_a_seated_slot_starts_from_zero_whatever_its_tenant_left(model,
                                                                 chunked):
    """Every slot's state is filled with junk before a request is seated:
    what it serves is what a fresh engine serves."""
    import jax.numpy as jnp

    prompt = _prompts(model[0], [45 if chunked else 20], seed=3)
    want = _serve(model, prompt, 10)
    eng = _engine(model)
    for k in STATE:
        eng.cache[k] = jnp.full_like(eng.cache[k], 7.0)
    req = eng.submit(prompt[0], max_new_tokens=10)
    _run(eng, [req])
    assert [req.output] == want
    eng.shutdown()


def test_a_decode_step_between_two_chunks_leaves_the_state_bit_for_bit(
        model):
    """One request decodes while a long prompt is prefilled a chunk a
    step: between two of its chunks the prefilling slot's state is what
    the last chunk left, to the bit, though a decode step ran."""
    cfg = model[0]
    short, long = _prompts(cfg, [9, 150], seed=4)
    eng = _engine(model)
    first = eng.submit(short, max_new_tokens=40)
    eng.step()
    assert first.slot in eng._active
    second = eng.submit(long, max_new_tokens=4)
    # No chunk goes ahead of a fetch here (PR 53): a decode step has to
    # fall between two chunks, with none of them behind it.
    eng._prefill_ahead = lambda: None
    eng.step()                       # seats it: chunk 1 and a decode step
    slot = second.slot
    assert slot in eng._prefilling and second.prefilled == 32
    seen = 0
    while slot in eng._prefilling:
        # What the chunk just dispatched left is what the next one reads:
        # run the decode alone (no chunk) by taking the tick away once.
        before = _state(eng, slot)
        tick, eng._prefill_tick = eng._prefill_tick, lambda: None
        assert eng.step() == 1       # the first request decoded
        eng._prefill_tick = tick
        after = _state(eng, slot)
        for k in STATE:
            assert before[k].any() and np.array_equal(before[k], after[k])
        eng.step()
        seen += 1
    assert seen >= 3
    _run(eng, [first, second])
    alone = _serve(model, [long], 4)
    assert [second.output] == alone
    eng.shutdown()


def test_pad_rows_name_the_scratch_row_and_repeat_the_last_rows_pages(
        model):
    """Three prompts admitted as one wave of four rows. The tables of
    every kind repeat the last real row for the pad row; the state's names
    the scratch row, and the wave leaves the fourth slot's state alone."""
    cfg = model[0]
    eng = _engine(model)
    for k in STATE:
        eng.cache[k] = eng.cache[k].at[:, :4].set(5.0)
    calls = []
    tables = eng._prefill_tables

    def spy(*args, **kw):
        out = tables(*args, **kw)
        calls.append({k: np.asarray(v) for k, v in out.items()})
        return out

    eng._prefill_tables = spy
    reqs = [eng.submit(p, max_new_tokens=3)
            for p in _prompts(cfg, [9, 17, 12], seed=5)]
    eng.step()
    seated = [r.slot for r in reqs]
    (out,) = calls
    # State: the pad row is the scratch row, never a real slot.
    assert out["slots"].tolist() == seated + [eng.slots]
    assert out["ends"].tolist() == [True] * 4
    # Every kind: the pad row repeats the last real row.
    for kind in ("full", "window", "window_first"):
        assert np.array_equal(out[kind][3], out[kind][2]), kind
    (free,) = set(range(4)) - set(seated)
    for k in STATE:
        assert (_state(eng, free)[k] == 5.0).all(), k
        assert not (_state(eng, seated[0])[k] == 5.0).all(), k
    _run(eng, reqs)
    eng.shutdown()


def test_a_bucket_over_the_models_cap_goes_as_waves_and_serves_the_same(
        model, monkeypatch):
    """``PREFILL_TOKENS_MAX`` under 4 x the bucket: four prompts of one
    bucket are admitted as a wave of three (four rows, one pad) and a wave
    of one. They serve what the unsplit engine serves, each wave names its
    own slots, and the pad row is the scratch row, so the slot no prompt
    took keeps what it held."""
    from ray_tpu.models import phi4flash_decode

    cfg = model[0]
    prompts = _prompts(cfg, [9, 14, 12, 16], seed=9)
    want = _serve(model, prompts, 8, slots=5, prefill_bucket=16)
    monkeypatch.setattr(phi4flash_decode, "PREFILL_TOKENS_MAX", 48)
    eng = _engine(model, slots=5, prefill_bucket=16)
    for k in STATE:
        eng.cache[k] = eng.cache[k].at[:, :5].set(5.0)
    calls = []
    tables = eng._prefill_tables

    def spy(*args, **kw):
        out = tables(*args, **kw)
        calls.append(np.asarray(out["slots"]).tolist())
        return out

    eng._prefill_tables = spy
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    seated = [r.slot for r in reqs]
    assert calls == [seated[:3] + [eng.slots], seated[3:]]
    assert eng._prefill_waves == 2
    (free,) = set(range(5)) - set(seated)
    for k in STATE:
        assert (_state(eng, free)[k] == 5.0).all(), k
    _run(eng, reqs)
    assert [r.output for r in reqs] == want
    eng.shutdown()


def test_a_preempted_request_resumes_to_the_same_tokens(model):
    """A pool too small for three long answers: the youngest is preempted,
    prefills again from position 0 (so from a zero state) and its stream
    goes on as if nothing had happened."""
    cfg = model[0]
    prompts = _prompts(cfg, [30, 30, 30], seed=6)
    want = _serve(model, prompts, 40)
    eng = _engine(model, pool_pages=40)
    reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
    _run(eng, reqs)
    assert eng.preempted >= 1
    assert [r.output for r in reqs] == want
    assert eng._pages.in_use == 0
    eng.shutdown()


def test_a_slot_reused_after_a_finish_serves_what_a_fresh_engine_serves(
        model):
    cfg = model[0]
    prompts = _prompts(cfg, [20, 45, 33, 70, 12, 50], seed=7)
    want = [_serve(model, [p], 6)[0] for p in prompts]
    eng = _engine(model, slots=2)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _run(eng, reqs)
    assert [r.output for r in reqs] == want
    eng.shutdown()


def test_a_mesh_whose_program_the_model_lacks_is_refused(model):
    with pytest.raises(ValueError, match="has no shard_decode_state"):
        _engine(model, mesh_shape=(1, 2))


@pytest.mark.parametrize("how", ["prefill_only", "adopt"])
def test_a_handoff_is_refused_where_it_is_asked_for(model, how):
    eng = _engine(model)
    prompt = _prompts(model[0], [10])[0]
    kw = {"prefill_only": True} if how == "prefill_only" \
        else {"adopt": {"page_tokens": 4, "committed_len": 10}}
    with pytest.raises(ValueError, match="slot state"):
        eng.submit(prompt, max_new_tokens=2, **kw)
    eng.shutdown()


def test_the_rows_and_the_launches_carry_the_state(model):
    cfg = model[0]
    eng = _engine(model)
    reqs = [eng.submit(p, max_new_tokens=5)
            for p in _prompts(cfg, [50, 9], seed=8)]
    _run(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    launches = [s for r in rows for s in r.get("slices", [])
                if s["name"] == "launch"]
    decodes = [s for s in launches if s.get("program") == "decode"]
    assert decodes and all(s["state_slots"] == s["batch"] for s in decodes)
    chunks = [s for s in launches if s.get("program") == "prefill_chunk"]
    # 50 tokens in chunks of 32: the first ends no prompt, the second does.
    assert [s["cross_rows"] for s in chunks] == [0, 1]
    assert [s["prefix"] for s in chunks] == [0, 32]
    whole = [s for s in launches if s.get("program") == "paged_prefill"]
    assert whole and whole[0]["cross_rows"] == 1 and whole[0]["prefix"] == 0
    held = [r["state_bytes"] for r in rows if "state_bytes" in r]
    assert max(held) == 2 * eng._slot_state_bytes and held[-1] == 0
    eng.shutdown()


def test_greedy_streams_are_those_of_an_engine_that_stands_back(model):
    """A chunk sent ahead of the fetch (PR 53) hands its state to the next
    as one at the tick's usual place does: tests/chunk_ahead_cases.py on a
    model with slot state."""
    cases.greedy_streams_are_those_of_an_engine_that_stands_back(
        lambda **kw: (_engine(model, **kw), model[0]))
