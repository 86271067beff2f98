"""Two kinds of page in the one allocator (``DecodeEngine``, "page kinds";
docs/SERVING.md): a model may say that some of its pool only has to
outlive a WINDOW of tokens. Driven at the debug preset of MiMo-V2
(``models/mimo.py``: window 12, so pages of 4 keep 4 a slot), on the CPU."""

import hashlib

import numpy as np
import pytest

import chunk_ahead_cases as cases

from ray_tpu.serve.paging import WindowPages

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _engine(**kw):
    import jax

    from ray_tpu.models import mimo, mimo_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg = mimo.PRESETS["debug"]
    params = mimo.init_params(cfg, jax.random.key(0))
    args = dict(slots=4, capacity=256, page_tokens=4,
                prefill_chunk_tokens=32, model=mimo_decode,
                step_timeline=4096, metrics_enabled=False,
                trace_spans=False)
    args.update(kw)
    return DecodeEngine(params, cfg, **args), cfg


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def _run(eng, reqs, steps=2000, each=None):
    for _ in range(steps):
        eng.step()
        if each is not None:
            each()
        if all(r.done.is_set() for r in reqs):
            return
    raise AssertionError("requests did not finish")


# ------------------------------------------------------------ WindowPages


def test_window_pages_grow_trim_and_release():
    w = WindowPages(pages=10, slots=2, slot_pages_max=16, page_tokens=4,
                    window=12)
    assert w.keep == 4
    w.seat(0, 0)
    assert w.missing(0, 9) == 3 and w.grow(0, 9) == 3
    assert w.slot_pages(0) == [1, 2, 3] and w.alloc.in_use == 3
    # The query at position 15 reads keys 4..15: page 0 is dead.
    assert w.trim(0, 15) == 1 and int(w.first[0]) == 1
    assert w.slot_pages(0) == [2, 3]
    # All-or-nothing: 8 free, 9 asked.
    assert w.grow(0, 4 * 12) is None and w.alloc.in_use == 2
    cols, first = w.columns(0, 15, 5)
    assert first == 1 and cols.tolist() == [2, 3, 0, 0, 0]
    assert w.span(30) == (4, 4)       # keys 19..29: pages 4..7
    # A freed page is handed out again, the last freed first.
    assert w.grow(0, 13) == 1 and w.slot_pages(0) == [2, 3, 1]
    assert w.release(0) == 3 and w.alloc.in_use == 0
    assert not w.table.any()


# ------------------------------------------------------------- the engine


def test_the_engine_keeps_a_table_a_kind_and_no_prefix_index():
    eng, _ = _engine()
    assert eng._kind == "full" and list(eng._windows) == ["window"]
    assert eng.prefix is None
    assert set(eng._leaf_kind.values()) == {"full", "window"}
    w = eng._windows["window"]
    # What the slots keep and eight prefills in flight, written through.
    assert w.alloc.pages == 4 * 4 + 4 * 8
    st = eng.stats()
    assert st["pages_in_use_by_kind"] == {"full": 0, "window": 0}
    assert st["pages_total_by_kind"]["window"] == w.alloc.pages
    eng.shutdown()


def test_a_model_of_one_kind_has_no_window_tables():
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    eng = DecodeEngine(llama.init_params(cfg, jax.random.key(0)), cfg,
                       slots=2, capacity=64, page_tokens=16,
                       step_timeline=64)
    assert eng._windows == {} and eng._kind == "full"
    assert eng.prefix is not None
    req = eng.submit([1, 2, 3], max_new_tokens=3)
    _run(eng, [req])
    row = eng.steplog.dump()["rows"][0]
    assert "pages_full" not in row and "kv_tokens" not in row
    assert "pages_in_use_by_kind" not in eng.stats()
    eng.shutdown()


def test_a_mesh_whose_program_the_model_lacks_is_refused():
    with pytest.raises(ValueError, match="has no shard_decode_state"):
        _engine(mesh_shape=(1, 1))


def test_window_pages_stay_bounded_and_both_allocators_come_back():
    """A long chunked prefill and a long decode: between steps a slot
    never holds more window pages than ``keep``; freed pages are used
    again; at the end both allocators are as they were found."""
    eng, cfg = _engine()
    w = eng._windows["window"]
    seen, most = set(), [0]

    def each():
        for slot in range(eng.slots):
            assert int(w.held[slot]) <= w.keep, (slot, w.held)
            seen.update(w.slot_pages(slot))
        most[0] = max(most[0], w.alloc.in_use)

    reqs = [eng.submit(p, max_new_tokens=m) for p, m in zip(
        _prompts(cfg, [150, 9, 70]), [60, 90, 40])]
    _run(eng, reqs, each=each)
    assert [r.status for r in reqs] == ["completed"] * 3
    # 150 + 60 tokens are 53 pages of 4; the window kind met far fewer
    # ids than it handed pages out: they were used again.
    handed = sum(e["n"] for r in eng.steplog.dump()["rows"]
                 for e in r.get("events", [])
                 if e["kind"] == "page-alloc"
                 and e["page_kind"] == "window")
    assert handed >= 53 and len(seen) < handed / 2
    assert most[0] <= eng.slots * w.keep
    assert eng._pages.in_use == 0 and w.alloc.in_use == 0
    assert w.alloc.free_count == w.alloc.pages
    assert not w.table.any() and not eng._block_tables.any()
    eng.shutdown()


def test_the_rows_the_launch_and_the_events_name_the_kinds():
    eng, cfg = _engine()
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(cfg, [40, 20])]
    _run(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    decoded = [r for r in rows if r.get("ctx_tokens")]
    assert decoded
    for r in decoded:
        assert r["pages_window"] <= eng.slots * 4
        # Both are taken at the step's end, a finished slot's gone.
        assert r["pages_full"] >= r["kv_tokens"] / 4
        assert "pages_pinned" not in r      # no prefix index here
        launch = next(s for s in r["slices"] if s["name"] == "launch"
                      and s.get("program") == "decode")
        assert launch["window_pages"] == eng.slots * 4
        # Every stepping context is past the window of 12.
        assert launch["window_tokens"] == 12 * launch["batch"]
        assert launch["moe_pairs"] >= 0
    chunk = [s for r in rows for s in r["slices"]
             if s["name"] == "launch"
             and s.get("program") == "prefill_chunk"]
    assert [s["prefix"] for s in chunk] == [0, 32]
    kinds = {(e["kind"], e["page_kind"]) for r in rows
             for e in r.get("events", []) if "page_kind" in e}
    assert kinds == {("page-alloc", "full"), ("page-alloc", "window"),
                     ("page-free", "full"), ("page-free", "window")}
    eng.shutdown()


def test_preemption_frees_both_kinds_and_the_stream_goes_on():
    """A full-kind pool too small for three long answers: the youngest
    request is preempted, comes back, and every answer is the one an
    engine with room gives."""
    small, cfg = _engine(pool_pages=40)
    roomy, _ = _engine()
    prompts = _prompts(cfg, [30, 30, 30], seed=3)
    out = []
    for eng in (small, roomy):
        reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
        _run(eng, reqs)
        out.append([r.output for r in reqs])
        assert eng._pages.in_use == 0
        assert eng._windows["window"].alloc.in_use == 0
    assert small.preempted > 0 and roomy.preempted == 0
    assert out[0] == out[1]
    small.shutdown()
    roomy.shutdown()


def test_a_dry_window_kind_pauses_admission_and_nothing_is_lost():
    eng, cfg = _engine(prefill_chunk_tokens=0, capacity=128)
    w = eng._windows["window"]
    # Leave the window kind 20 pages: one 64-token prompt at a time.
    held = w.alloc.alloc(w.alloc.pages - 20)
    reqs = [eng.submit(p, max_new_tokens=4)
            for p in _prompts(cfg, [64, 64, 64])]
    _run(eng, reqs)
    assert [r.status for r in reqs] == ["completed"] * 3
    w.alloc.free(held)
    assert w.alloc.in_use == 0 and eng._pages.in_use == 0
    eng.shutdown()


def test_a_handoff_carries_both_kinds():
    """Prefill on one engine, decode on another: the payload has the full
    kind's pages of the whole prompt and the window kind's from the
    window's first page on, and the adopted stream is the colocated one."""
    a, cfg = _engine()
    b, _ = _engine()
    c, _ = _engine()
    prompt = _prompts(cfg, [45], seed=5)[0]
    pre = a.submit(prompt, max_new_tokens=1, prefill_only=True)
    _run(a, [pre])
    h = pre.handoff
    # Keys 34..44 are the next query's window: pages 8..11.
    assert a._windows["window"].span(45) == (8, 4)
    assert h["full_k"].shape[1] == 12 and h["window_k"].shape[1] == 4
    adopt = {k: v for k, v in h.items() if k != "nbytes"}
    got = b.submit(prompt, max_new_tokens=12, adopt=adopt)
    want = c.submit(prompt, max_new_tokens=12)
    _run(b, [got])
    _run(c, [want])
    assert got.output == want.output and len(got.output) == 12
    for eng in (a, b, c):
        assert eng._pages.in_use == 0
        assert eng._windows["window"].alloc.in_use == 0
        eng.shutdown()
    from ray_tpu.core.errors import HandoffAdoptError

    bad = dict(adopt, window_k=adopt["window_k"][:, :2])
    with pytest.raises(HandoffAdoptError):
        b.submit(prompt, max_new_tokens=2, adopt=bad)


# ------------------------------- a window kind as large as the full kind


def _wide_engine(**kw):
    """Command A+'s debug preset with a window of 256 tokens over pages
    of 4: 65 window pages a slot, as the served model keeps at a window
    of 4,096 over pages of 64."""
    import dataclasses

    import jax

    from ray_tpu.models import cohere2_moe, cohere2_moe_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg = dataclasses.replace(cohere2_moe.PRESETS["debug"], window=256)
    params = cohere2_moe.init_params(cfg, jax.random.key(0))
    args = dict(slots=3, capacity=512, page_tokens=4,
                prefill_chunk_tokens=64, model=cohere2_moe_decode,
                step_timeline=4096, metrics_enabled=False,
                trace_spans=False)
    args.update(kw)
    return DecodeEngine(params, cfg, **args), cfg


def test_sixty_five_window_pages_a_slot_are_kept_trimmed_and_viewed():
    """The window kind at the size of the full kind: a slot keeps 65
    pages between steps, a chunk's table has the chunk's and the window's
    columns, the decode's view lists 65 pages a slot, and the streams are
    the reference's."""
    from benchmarks.reference import cohere2_moe_ref

    eng, cfg = _wide_engine()
    w = eng._windows["window"]
    assert w.keep == 65
    # What the slots keep and four chunks in flight, written through.
    assert w.alloc.pages == 3 * 65 + 4 * 16
    tables = eng._prefill_tables([0], [0], eng._block_tables[:1, :16], 64)
    assert tables["window"].shape == (1, (64 + 256) // 4 + 1)
    most = [0]

    def each():
        for slot in range(eng.slots):
            assert int(w.held[slot]) <= w.keep, (slot, w.held)
        most[0] = max(most[0], int(max(w.held)))

    prompts = _prompts(cfg, [300, 30, 410])
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, [12, 20, 9])]
    _run(eng, reqs, each=each)
    assert most[0] == 65
    rows = eng.steplog.dump()["rows"]
    launches = [s for r in rows for s in r["slices"]
                if s["name"] == "launch" and s.get("program") == "decode"]
    assert launches and all(s["window_pages"] == 3 * 65 for s in launches)
    # A context under the window counts whole, one over it the window.
    assert max(s["window_tokens"] for s in launches) <= 2 * 256 + 50
    assert any(r.get("pages_window", 0) > 100 for r in rows)
    margins = cohere2_moe_ref.served_token_margins(
        eng.params, cfg, prompts, [r.output for r in reqs])
    assert len(margins) == 41 and max(margins) < 2e-4
    assert eng._pages.in_use == 0 and w.alloc.in_use == 0
    eng.shutdown()


def test_a_handoff_carries_sixty_five_window_pages():
    a, cfg = _wide_engine()
    b, _ = _wide_engine()
    c, _ = _wide_engine()
    prompt = _prompts(cfg, [330], seed=5)[0]
    pre = a.submit(prompt, max_new_tokens=1, prefill_only=True)
    _run(a, [pre])
    h = pre.handoff
    # Keys 75..329 are the next query's window: pages 18..82.
    assert a._windows["window"].span(330) == (18, 65)
    assert h["full_k"].shape[1] == 83 and h["window_k"].shape[1] == 65
    adopt = {k: v for k, v in h.items() if k != "nbytes"}
    got = b.submit(prompt, max_new_tokens=6, adopt=adopt)
    want = c.submit(prompt, max_new_tokens=6)
    _run(b, [got])
    _run(c, [want])
    assert got.output == want.output and len(got.output) == 6
    for eng in (a, b, c):
        assert eng._pages.in_use == 0
        assert eng._windows["window"].alloc.in_use == 0
        eng.shutdown()


# ------------------------------------- the models of one kind, unchanged

# sha256 (first 16 hex) of the lowered text of the engine's programs at
# the parent of PR 43 (cdaa02b), llama and deepseek at toy sizes: page
# kinds, the router's score and its bias left them letter for letter; and
# mimo's at the parent of PR 45 (6eada8f), which slot state, the prefill
# rows' slots and the wave's cap left so; and phi4flash's at the parent of
# PR 48 (3fd825d), which the engine's two other ways to step left so (and
# PR 49, which moved their layer loop and view into ``moe_decode.py`` and
# gave the chunk kernel tiles from its shape); cohere2's as PR 49 made it.
# The three DECODE programs of the models that prefill through
# ``ops/chunk_attention.py`` stand as they were; their two prefill
# programs are PR 51's, whose kernel takes several heads a program.
# deepseek's decode is PR 56's, which reads the latent pages through
# ``ops/paged_decode_attention.py``'s one-pool form; the two-pool form
# that phi4flash's and cohere2's decode run stayed letter for letter.
LOWERED_AT_PARENT = {
    "llama.decode": "5ee1c9392ee387ff",
    "llama.paged_prefill": "e3bd72ad3a1c0d98",
    "llama.paged_suffix": "3a16d93924c32163",
    "deepseek.decode": "a20d3c25fd5b6e39",
    "deepseek.paged_prefill": "95ae7bc75e2000da",
    "deepseek.paged_suffix": "2f96485ef153e703",
    "mimo.decode": "a40ff6a77742dffe",
    "mimo.paged_prefill": "d5b19c3e8ae359a3",
    "mimo.paged_suffix": "9ed41fae6bb7cc72",
    "phi4flash.decode": "dd5296a9fa92db6c",
    "phi4flash.paged_prefill": "fef1dc4950b0d1ad",
    "phi4flash.paged_suffix": "6db1ea7b98e8e5bd",
    "cohere2.decode": "b0399019312b96dd",
    "cohere2.paged_prefill": "4365089b8421dde5",
    "cohere2.paged_suffix": "5747e90ae441d88a",
}


@pytest.mark.parametrize("name", ["llama", "deepseek", "mimo", "phi4flash",
                                  "cohere2"])
def test_one_kind_models_lower_to_the_text_they_had(name):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (cohere2_moe, cohere2_moe_decode, deepseek,
                                deepseek_decode, llama, llama_decode, mimo,
                                mimo_decode, phi4flash, phi4flash_decode)
    from ray_tpu.serve.decode import DecodeEngine

    mod, dec, cfg = {
        "llama": (llama, llama_decode, llama.LlamaConfig(
            vocab_size=61, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=64, max_seq_len=128)),
        "deepseek": (deepseek, deepseek_decode, deepseek.PRESETS["debug"]),
        "mimo": (mimo, mimo_decode, mimo.PRESETS["debug"]),
        "phi4flash": (phi4flash, phi4flash_decode,
                      phi4flash.PRESETS["debug"]),
        "cohere2": (cohere2_moe, cohere2_moe_decode,
                    cohere2_moe.PRESETS["debug"]),
    }[name]
    eng = DecodeEngine(mod.init_params(cfg, jax.random.key(0)), cfg,
                       slots=4, capacity=128, page_tokens=16,
                       prefill_chunk_tokens=32, model=dec, step_timeline=0,
                       metrics_enabled=False, trace_spans=False)
    state = jnp.asarray(eng._host_state())
    temps = jnp.zeros((4,), jnp.float32)
    view = jax.tree.map(jnp.asarray, eng._live_view(eng._block_tables,
                                                    eng._slot_pages))
    one = jnp.zeros((1,), jnp.int32)

    def tables(width):
        """A model of one kind takes the table itself."""
        return eng._prefill_tables([0], [0], eng._block_tables[:1, :width],
                                   16)

    draw = (jnp.zeros((1,), jnp.float32), jnp.asarray(0, jnp.int32))
    lowered = {
        "decode": eng._decode.lower(eng.params, eng.cache, state, view,
                                    temps),
        "paged_prefill": eng._paged_prefill.lower(
            eng.params, eng.cache, jnp.zeros((1, 16), jnp.int32), one,
            tables(1), one, *draw, n=1, bucket=16),
        "paged_suffix": eng._paged_suffix.lower(
            eng.params, eng.cache, jnp.zeros((1, 16), jnp.int32), one, one,
            tables(2), one, *draw, n=1, bucket=16, width=2),
    }
    got = {f"{name}.{key}": hashlib.sha256(
        low.as_text().encode()).hexdigest()[:16]
        for key, low in lowered.items()}
    assert got == {k: v for k, v in LOWERED_AT_PARENT.items()
                   if k.startswith(name + ".")}, got
    eng.shutdown()


# ------------------------------------------- a chunk sent ahead (PR 53)
# The cases of tests/chunk_ahead_cases.py on a model with page kinds: a
# window page is a page that can leak.


def _make(**kw):
    return _engine(**kw)


def test_greedy_streams_are_those_of_an_engine_that_stands_back():
    cases.greedy_streams_are_those_of_an_engine_that_stands_back(_make)


def test_no_two_chunks_lie_between_two_decodes():
    cases.no_two_chunks_lie_between_two_decodes(_make)


@pytest.mark.parametrize("how", ["cancel", "deadline", "preempt",
                                 "shutdown"])
def test_a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(how):
    cases.a_request_that_goes_with_its_chunk_in_flight_leaks_nothing(
        _make, how)


def test_a_dry_window_kind_stands_the_ahead_tick_back():
    cases.a_dry_free_list_stands_the_ahead_tick_back(
        _make, lambda eng: eng._windows["window"].alloc)
