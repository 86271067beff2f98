"""An engine over a model with slot state beside ONE kind of page
(``DecodeEngine`` with ``_state_leaves`` and no window kind;
docs/SERVING.md, "Slot state"): the allocator, block table and view of a
model of one kind, and beside them state the engine never looks into.
Driven at the debug preset of Nemotron-H (``models/nemotron_h.py``: Mamba-2
layers, two attention layers, held experts in a latent width), on the CPU,
and held to the plain reference's own greedy choice."""

import numpy as np
import pytest

import chunk_ahead_cases as cases

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.PRESETS["debug"]
    return cfg, nemotron_h.init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    from ray_tpu.models import nemotron_h_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    args = dict(slots=3, capacity=128, page_tokens=8,
                prefill_chunk_tokens=32, model=nemotron_h_decode,
                step_timeline=4096, metrics_enabled=False,
                trace_spans=False)
    args.update(kw)
    return DecodeEngine(params, cfg, **args)


def _margins(model, prompts, reqs):
    from benchmarks.reference import nemotron_h_ref

    cfg, params = model
    return nemotron_h_ref.served_token_margins(
        params, cfg, prompts, [list(r.output) for r in reqs])


def test_the_engine_keeps_one_kind_of_page_and_state_beside_it(model):
    eng = _engine(model)
    assert eng._kind == "full" and eng._windows == {}
    assert eng._state_leaves == ("ssm", "conv") and eng.prefix is None
    assert set(eng.cache) == {"full_k", "full_v", "ssm", "conv", "length"}
    assert eng.pages_in_use() == {"full": 0}
    cfg = model[0]
    per_slot = cfg.kind_layers("mamba") * (
        cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state * 4
        + (cfg.d_conv - 1) * cfg.conv_dim * 4)
    assert eng.stats()["state_bytes_total"] == 3 * per_slot
    eng.shutdown()


def test_streams_are_the_references_choice_and_rows_carry_state_and_tokens(
        model):
    """Seven requests over three slots, whole prefills and chunked ones: a
    reused slot starts from zero (every stream is the reference's own
    greedy choice, which starts from nothing), and the step log's rows
    carry ``state_bytes``, ``kv_tokens`` and the kind's pages."""
    eng = _engine(model)
    cfg = model[0]
    prompts = cases.prompts(cfg, (9, 70, 33, 20, 100, 12, 41))
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    cases.run(eng, reqs)
    assert all(len(r.output) == 6 and r.status == "completed" for r in reqs)
    assert max(_margins(model, prompts, reqs)) < 1e-3
    rows = [r for r in eng.steplog.dump()["rows"] if r.get("kv_tokens")]
    assert rows and all("state_bytes" in r and "pages_full" in r
                        for r in rows)
    per_slot = eng.stats()["state_bytes_total"] // 3
    assert {r["state_bytes"] // per_slot for r in rows} <= {1, 2, 3}
    assert max(r["pages_full"] * 8 for r in rows) >= max(
        r["kv_tokens"] for r in rows)
    decodes = [s for r in eng.steplog.dump()["rows"] for s in r["slices"]
               if s["name"] == "launch" and s.get("program") == "decode"]
    assert decodes and all("state_slots" in s and "moe_pairs" in s
                           for s in decodes)
    cases.nothing_leaked(eng)
    eng.shutdown()


def test_a_preempted_request_prefills_again_from_a_zero_state(model):
    """A pool too small for three long answers: the youngest is preempted
    for pages, comes back, prefills prompt and answer so far from position
    0 (a zero state) and still says what the reference says."""
    eng = _engine(model, pool_pages=14)
    cfg = model[0]
    prompts = cases.prompts(cfg, (30, 28, 26), seed=5)
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    cases.run(eng, reqs)
    assert eng.stats()["preempted"] > 0
    assert all(len(r.output) == 24 for r in reqs)
    assert max(_margins(model, prompts, reqs)) < 1e-3
    cases.nothing_leaked(eng)
    eng.shutdown()


@pytest.mark.parametrize("how", ["prefill_only", "adopt"])
def test_a_handoff_is_refused_where_it_is_asked_for(model, how):
    eng = _engine(model)
    kw = ({"prefill_only": True} if how == "prefill_only"
          else {"adopt": {"pages": {}, "length": 8}})
    with pytest.raises(ValueError, match="slot state"):
        eng.submit(cases.prompts(model[0], (16,))[0], max_new_tokens=2, **kw)
    eng.shutdown()


def test_a_mesh_is_refused(model):
    with pytest.raises(ValueError, match="shard_decode_state"):
        _engine(model, mesh_shape=(2,))


# ------------------------------------------- a chunk sent ahead (PR 53)
# The cases of tests/chunk_ahead_cases.py on this model: state beside one
# kind of page.


def _make_for(model):
    def make(**kw):
        return _engine(model, **kw), model[0]
    return make


def test_greedy_streams_are_those_of_an_engine_that_stands_back(model):
    cases.greedy_streams_are_those_of_an_engine_that_stands_back(
        _make_for(model))


def test_no_two_chunks_lie_between_two_decodes(model):
    cases.no_two_chunks_lie_between_two_decodes(_make_for(model))


@pytest.mark.parametrize("how", ["cancel", "deadline", "preempt",
                                 "shutdown"])
def test_a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(
        model, how):
    cases.a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(
        _make_for(model), how)


def test_a_dry_free_list_stands_the_ahead_tick_back(model):
    cases.a_dry_free_list_stands_the_ahead_tick_back(
        _make_for(model), lambda eng: eng._pages)
