"""PR 24: the engine step tells its own time. A row's ``slices`` tile the
step on the step log's clock (and are ``engine:<name>`` annotations on the
profiler's), the phases stay what they were, the ``preempt`` event counts
what it throws away, ``first_token_at`` is set once, the jitted programs and
the kernels carry stable names, and the whole of it costs microseconds a
step. All CPU, tiny configs: tier-1."""

import re
import time

import numpy as np
import pytest

SLICE_NAMES = {"park", "reap", "admit", "pages", "launch", "fetch",
               "sample_emit", "finish"}


def _tiny(max_seq_len=512):
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64,
                            max_seq_len=max_seq_len)
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _engine(**kw):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    args = dict(slots=4, capacity=256, page_tokens=16, pool_pages=40,
                prefill_bucket=16, prefix_pool_entries=0)
    args.update(kw)
    return cfg, DecodeEngine(params, cfg, **args)


def _drive(eng, reqs, budget=3000):
    for _ in range(budget):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError(f"not done: {[r.status for r in reqs]}")


def _prompts(cfg, lengths, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


SCENARIOS = {
    # name: (engine arguments, prompt lengths, new tokens, submit arguments
    #        [, leading tokens every prompt shares with the first])
    "admission": ({}, [10, 30, 12], 12, {}),
    "admission_wave_of_equal_prompts": ({}, [12, 12, 12, 12], 6, {}),
    "chunked_prefill": ({"prefill_chunk_tokens": 32}, [100, 20, 70], 10, {}),
    "preemption": ({"pool_pages": 20}, [30, 30, 30, 30], 90, {}),
    "preemption_mid_prefill": (
        {"pool_pages": 12, "prefill_chunk_tokens": 32}, [10, 70, 30, 90],
        20, {}),
    "sampled_path": ({}, [10, 30], 12, {"temperature": 0.7}),
    "prefix_hit": ({"slots": 1, "prefix_pool_entries": 4,
                    "prefix_match_min_tokens": 16}, [40, 44, 38], 6, {}, 32),
}


def _run(name):
    kw, lengths, new, sub, *shared = SCENARIOS[name]
    cfg, eng = _engine(**kw)
    prompts = _prompts(cfg, lengths)
    if shared:
        prompts = [prompts[0][:shared[0]] + p[shared[0]:] for p in prompts]
    reqs = [eng.submit(p, max_new_tokens=new, **sub) for p in prompts]
    _drive(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    eng.shutdown()
    return eng, reqs, rows


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_slices_tile_the_step_with_no_hole_or_overlap(name):
    eng, reqs, rows = _run(name)
    assert rows and all(r.status == "completed" for r in reqs)
    if name.startswith("preemption"):
        assert eng.preempted > 0
    if name == "prefix_hit":  # the suffix admission behind spliced pages
        assert eng.prefix.stats()["hits"] == 2
    seen = set()
    for i, row in enumerate(rows):
        slices = row["slices"]
        seen |= {s["name"] for s in slices}
        assert {s["name"] for s in slices} <= SLICE_NAMES
        body = [s for s in slices if s["name"] != "park"]
        # ``park`` leads, outside the row: previous step's end to t0.
        assert slices[:len(slices) - len(body)] == [
            s for s in slices if s["name"] == "park"][:1]
        if slices[0]["name"] == "park":
            assert slices[0]["t1"] == row["t0"]
            assert slices[0]["t0"] <= slices[0]["t1"]
            if i:
                assert slices[0]["t0"] >= rows[i - 1]["t1"]
        assert body[0]["name"] == "reap"
        assert body[0]["t0"] == row["t0"] and body[-1]["t1"] == row["t1"]
        for a, b in zip(body, body[1:]):
            assert a["t1"] == b["t0"] and a["t0"] <= a["t1"]
        total = sum(s["t1"] - s["t0"] for s in body)
        assert total == pytest.approx(row["t1"] - row["t0"], abs=1e-9)
        # Every device call of the step is a launch, tagged by program.
        for s in body:
            if s["name"] in ("launch", "fetch"):
                assert isinstance(s["program"], str) and s["program"]
    assert {"reap", "admit", "launch", "fetch", "sample_emit",
            "finish"} <= seen
    assert ("pages" in seen) and ("park" in seen)


@pytest.mark.parametrize("name", ["admission", "chunked_prefill",
                                  "sampled_path"])
def test_phases_are_what_they_were(name):
    _, _, rows = _run(name)
    kinds = set()
    for row in rows:
        for ph in row["phases"]:
            kinds.add(ph["phase"])
            assert row["t0"] <= ph["t0"] <= ph["t1"] <= row["t1"]
            if ph["phase"] == "admit":
                # From the step's start, over reap and the whole admission.
                assert ph["t0"] == row["t0"] and ph["waves"] >= 1
            if ph["phase"] == "decode":
                assert ph["batch"] >= 1
                run = [s for s in row["slices"]
                       if s.get("program") == "decode"]
                # From before the dispatch to after the blocking fetch.
                assert [s["name"] for s in run] == ["launch", "fetch"]
                assert ph["t0"] <= run[0]["t0"] and run[1]["t1"] <= ph["t1"]
    assert kinds == ({"admit", "decode", "prefill_chunk"}
                     if name == "chunked_prefill" else {"admit", "decode"})
    assert all(set(r) >= {"t0", "t1", "phases", "slices", "active",
                          "prefilling", "queued", "pages_free"}
               for r in rows)


def test_launch_slices_say_program_role_and_tokens():
    _, reqs, rows = _run("chunked_prefill")
    launches = [s for r in rows for s in r["slices"]
                if s["name"] == "launch"]
    by = {}
    for s in launches:
        by.setdefault(s["program"], []).append(s)
    # 100 and 70 tokens go in chunks of 32 (one jitted program,
    # ``paged_suffix``, in the ROLE ``prefill_chunk``); 20 fits one wave.
    assert sorted(s["tokens"] for s in by["prefill_chunk"]) == sorted(
        [32, 32, 32, 4, 32, 32, 6])
    assert [s["tokens"] for s in by["paged_prefill"]] == [20]
    assert "paged_suffix" not in by

    decode = by["decode"]
    assert all(s["batch"] >= 1 and s["ctx_tokens"] >= s["batch"]
               for s in decode)
    # Row counters: the KV positions the decode needs, the pinned pages.
    for r in rows:
        d = [s for s in r["slices"] if s.get("program") == "decode"
             and s["name"] == "launch"]
        if d:
            assert r["ctx_tokens"] == d[0]["ctx_tokens"]
        assert "pages_pinned" not in r      # no prefix index here
    # ctx_tokens is the contexts' sum, the new token included.
    first = next(r for r in rows if "ctx_tokens" in r)
    assert first["ctx_tokens"] == 20 + 1


@pytest.mark.parametrize("name", ["admission", "chunked_prefill",
                                  "sampled_path"])
def test_a_step_fetches_ids_and_uploads_what_the_host_changed(name):
    """The counters that say the sample is taken in the program: every
    ``fetch`` reads ``bytes``, 4 a slot plus the step counter for a
    decode (no model counters here) and 4 a prompt for a prefill, never
    a row of the vocabulary; the decode's ``launch`` reads ``uploads``,
    1 (the view) on a step that follows a step with nothing written in
    between, and more only after the host wrote tokens (an admission, a
    finish) or a temperature."""
    eng, reqs, rows = _run(name)
    uploads = []
    for row in rows:
        wrote = False  # did the HOST seat or free a slot before the decode
        for s in row["slices"]:
            if s["name"] == "fetch" and s["program"] == "decode":
                assert s["bytes"] == 4 * (eng.slots + 1)
            elif s["name"] == "fetch":
                assert 4 <= s["bytes"] <= 4 * eng.slots
                wrote = True
            elif s["name"] == "launch" and s["program"] == "decode":
                uploads.append((s["uploads"], wrote))
        if any(s["name"] == "finish" for s in row["slices"]):
            uploads.append((None, True))  # the next decode re-uploads
    assert uploads and all(
        s.get("bytes", 0) < 4 * 61 for r in rows for s in r["slices"])
    follows = [n for (n, wrote), (_, before) in zip(uploads[1:], uploads)
               if n is not None and not wrote and not before]
    assert len(follows) >= 5 and set(follows) == {1}, uploads
    after = [n for n, wrote in uploads if n is not None and wrote]
    assert after and all(n in (2, 3) for n in after), uploads
    # A temperature goes up when a slot's changes, not every step.
    threes = sum(1 for n, _ in uploads if n == 3)
    assert threes <= (3 if name == "sampled_path" else 1), uploads


class _ViewSpy:
    """``llama_decode`` as the engine sees it, noting each view built."""

    def __init__(self, ld):
        self._ld = ld
        self.views = []          # (live pages, rows of the view)

    def __getattr__(self, name):
        return getattr(self._ld, name)

    def live_page_view(self, tables, counts, rows):
        self.views.append((int(np.sum(counts)), rows))
        return self._ld.live_page_view(tables, counts, rows)


def test_decode_reads_the_smallest_rung_that_holds_its_pages():
    """Pages of 4 tokens and 4 slots of 32 pages make the ladder (64,
    128), from the geometry alone. Contexts that grow past 64 pages
    are read on the lower rung, then on the upper; the decode's
    ``launch`` slice and its row say which (``view_pages``)."""
    cfg, eng = _engine(page_tokens=4, capacity=128, pool_pages=128)
    assert eng._view_ladder == (64, 128)
    eng._ld = spy = _ViewSpy(eng._ld)
    reqs = [eng.submit(p, max_new_tokens=10)
            for p in _prompts(cfg, [3, 39, 88, 110])]
    _drive(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    eng.shutdown()
    assert spy.views and {n for _, n in spy.views} == {64, 128}
    for live, n in spy.views:
        assert n == (64 if live <= 64 else 128), (live, n)
    widths = []
    for r in rows:
        d = [s for s in r["slices"] if s["name"] == "launch"
             and s.get("program") == "decode"]
        assert ("view_pages" in r) == bool(d)
        if d:
            assert r["view_pages"] == d[0]["view_pages"]
            # The view holds the context: fill is at most 1.
            assert r["ctx_tokens"] <= r["view_pages"] * 4
            widths.append(r["view_pages"])
    assert widths == [n for _, n in spy.views]


def test_a_decodes_launch_says_how_many_rows_of_its_view_hold_a_page():
    """``live_pages`` beside ``view_pages``: the rows of the rung that hold
    a page of a stepping slot (what a kernel over page lists fetches), so
    ``view_pages - live_pages`` rows are padding and ``live_pages x
    page_tokens - ctx_tokens`` tokens are fetched and masked."""
    cfg, eng = _engine(page_tokens=4, capacity=128, pool_pages=128)
    eng._ld = spy = _ViewSpy(eng._ld)
    reqs = [eng.submit(p, max_new_tokens=10)
            for p in _prompts(cfg, [3, 39, 88, 110])]
    _drive(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    eng.shutdown()
    launches = [s for r in rows for s in r["slices"]
                if s["name"] == "launch" and s.get("program") == "decode"]
    assert launches and len(launches) == len(spy.views)
    for s, (live, _) in zip(launches, spy.views):
        assert s["live_pages"] == live
        assert 0 < s["live_pages"] <= s["view_pages"]
        # Every token of the context lies on a live page, and a slot's
        # last page alone may be part empty.
        assert s["ctx_tokens"] <= s["live_pages"] * 4
        assert s["live_pages"] * 4 - s["ctx_tokens"] < 4 * s["batch"]
    # No other program's launch carries it.
    assert not any("live_pages" in s for r in rows for s in r["slices"]
                   if s.get("program") != "decode")


def test_a_deployment_meets_no_rung_for_the_first_time_under_traffic():
    """Built with the program's defaults, a deployment has dispatched
    ``decode`` at every rung before it takes a request: requests of mixed
    lengths then climb the ladder, and every ``jit-compile`` event of a
    ``decode`` key is older than the first of them."""
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    cfg, _ = _tiny()
    dep = LlamaDecodeDeployment(config=cfg, slots=4, capacity=128,
                                kv_page_tokens=4, kv_pool_pages=128)
    eng = dep.engine
    try:
        assert {("decode", 64), ("decode", 128)} <= eng._compiled
        assert np.asarray(eng.cache["length"]).sum() == 0
        keys = {k for k in eng._compiled if k[0] == "decode"}
        t_ready = time.time()
        reqs = [eng.submit(p, max_new_tokens=10)
                for p in _prompts(cfg, [3, 39, 88, 110])]
        for r in reqs:
            assert r.done.wait(60) and r.status == "completed"
        rows = eng.timeline()["rows"]
    finally:
        eng.shutdown()
    assert {r["view_pages"] for r in rows if "view_pages" in r} == {64, 128}
    assert {k for k in eng._compiled if k[0] == "decode"} == keys
    compiled = [e for r in rows for e in r.get("events", ())
                if e["kind"] == "jit-compile"]
    assert any(e["key"] == "decode/128" for e in compiled)
    assert all(e["ts"] < t_ready for e in compiled
               if e["key"].startswith("decode"))
    assert any(e["ts"] > t_ready for e in compiled)     # the prefills


def test_pages_pinned_rides_on_rows_with_a_prefix_index():
    cfg, eng = _engine(prefix_pool_entries=8)
    shared = _prompts(cfg, [48])[0]
    reqs = [eng.submit(shared + [i], max_new_tokens=4) for i in range(3)]
    _drive(eng, reqs)
    rows = eng.steplog.dump()["rows"]
    assert all("pages_pinned" in r for r in rows)
    assert max(r["pages_pinned"] for r in rows) >= 2
    assert rows[-1]["pages_pinned"] == eng.prefix.pinned_pages
    eng.shutdown()


def test_preempt_event_counts_what_it_throws_away():
    """One 64-token prompt decoding, then a 160-token prompt, both
    prefilled in chunks of 32, against a pool that cannot hold both: the
    younger one is preempted mid-prefill, and its chunks so far are the
    discarded work."""
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    from benchmarks import progtrace

    cfg, eng = _engine(pool_pages=12, prefill_chunk_tokens=32)
    old, young = _prompts(cfg, [64, 160])
    r_old = eng.submit(old, max_new_tokens=120)
    for _ in range(4):
        eng.step()
    r_young = eng.submit(young, max_new_tokens=4)
    while not eng.preempted:
        eng.step()
    rows = eng.steplog.dump()["rows"]
    ev = [e for r in rows for e in r.get("events", [])
          if e["kind"] == "preempt"]
    assert len(ev) == 1 and ev[0]["request"] == r_young.request_id
    launched = sum(s["tokens"] for r in rows for s in r["slices"]
                   if s["name"] == "launch"
                   and s["program"] == "prefill_chunk")
    thrown = launched - 64       # the older prompt's two chunks stay
    assert ev[0]["tokens"] == 0                      # nothing emitted
    assert ev[0]["prefilled"] == thrown > 0          # all of it redone
    assert ev[0]["pages"] == -(-thrown // 16)
    assert progtrace.prefill_useful_ratio(rows) == pytest.approx(
        1 - thrown / launched)
    # The slice that did it is ``pages``.
    row = next(r for r in rows if any(e["kind"] == "preempt"
                                      for e in r.get("events", [])))
    pages = next(s for s in row["slices"] if s["name"] == "pages")
    assert pages["t0"] <= ev[0]["ts"] <= pages["t1"]
    eng.shutdown()
    assert r_old.status != "failed"


def test_first_token_at_survives_a_readmission():
    cfg, eng = _engine(pool_pages=20)
    reqs = [eng.submit(p, max_new_tokens=90)
            for p in _prompts(cfg, [30] * 4)]
    firsts = {}
    for _ in range(3000):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
        for r in reqs:
            if r.first_token_at is not None:
                firsts.setdefault(r.request_id, r.first_token_at)
    again = [r for r in reqs if r.preemptions]
    assert again and all(r.status == "completed" for r in reqs)
    for r in reqs:
        assert r.first_token_at == firsts[r.request_id]
        assert r.submitted_at <= r.admitted_at <= r.first_token_at \
            <= r.finished_at
    eng.shutdown()


def test_ring_off_records_and_annotates_nothing():
    cfg, eng = _engine(step_timeline=0)
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(cfg, [10, 30])]
    _drive(eng, reqs)
    assert not eng.steplog.enabled and eng.steplog._annotate is None
    assert eng.steplog._slices == [] and eng.steplog._open is None
    assert eng.steplog.dump()["rows"] == []
    eng.shutdown()


def test_warmup_dispatches_belong_to_no_row():
    cfg, eng = _engine()
    eng.warm_decode()
    reqs = [eng.submit(p, max_new_tokens=4) for p in _prompts(cfg, [10])]
    _drive(eng, reqs)
    first = eng.steplog.dump()["rows"][0]
    assert first["slices"][0]["name"] == "reap"
    assert first["slices"][0]["t0"] == first["t0"]
    eng.shutdown()


def test_timeline_renders_slices_on_a_second_track():
    from ray_tpu.serve.steplog import timeline_chrome_events

    _, _, rows = _run("admission")
    ev = timeline_chrome_events({"rows": rows}, pid="engine:t")
    host = [e for e in ev if e.get("tid") == "engine-host"]
    assert {e["name"] for e in host} >= {"reap", "admit", "launch", "fetch",
                                         "sample_emit", "park"}
    launch = next(e for e in host if e["name"] == "launch")
    assert launch["ph"] == "X" and launch["args"]["program"]
    assert any(e.get("tid") == "engine-step" and e["name"] == "decode"
               for e in ev)


def test_slices_are_annotations_on_the_profilers_clock(tmp_path):
    """What the step log records is also in a profiler trace, by name and
    with the attributes as stats (read with JAX's own reader)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    cfg, eng = _engine(prefill_chunk_tokens=32)
    warm = [eng.submit(p, max_new_tokens=3) for p in _prompts(cfg, [70, 9])]
    _drive(eng, warm)
    jax.profiler.start_trace(str(tmp_path))
    reqs = [eng.submit(p, max_new_tokens=3) for p in _prompts(cfg, [70, 9])]
    _drive(eng, reqs)
    jax.profiler.stop_trace()
    eng.shutdown()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = [(e.name, dict(e.stats))
              for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name.startswith("engine:")]
    names = {n for n, _ in events}
    assert names >= {"engine:" + s for s in (
        "park", "reap", "admit", "pages", "launch", "fetch", "sample_emit",
        "finish")}
    programs = {st.get("program") for n, st in events
                if n == "engine:launch"}
    assert programs >= {"prefill_chunk", "paged_prefill", "decode"}
    assert any(st.get("tokens") == 32 for n, st in events
               if n == "engine:launch")
    assert any(n.startswith("PjitFunction(engine_decode)")
               for p in ProfileData.from_file(path).planes
               for line in p.lines for n in {e.name for e in line.events})


# ------------------------------------------- a chunk sent ahead (PR 53)


def test_a_step_that_dispatched_ahead_still_tiles():
    """The chunk of the NEXT step is a ``launch`` slice with ``ahead=1``
    between the decode's launch and its fetch (its preparation is
    ``admit``, as at the tick's usual place); the row carries the
    ``prefill_chunk`` phase; what the decode counted goes to the decode's
    launch, not to the newest one."""
    _, reqs, rows = _run("chunked_prefill")
    went = [r for r in rows if any(s.get("ahead") for s in r["slices"])]
    assert len(went) >= 4
    for row in went:
        names = [(s["name"], s.get("program")) for s in row["slices"]]
        at = next(i for i, s in enumerate(row["slices"]) if s.get("ahead"))
        assert names[at - 2:at + 2] == [
            ("launch", "decode"), ("admit", None),
            ("launch", "prefill_chunk"), ("fetch", "decode")]
        ahead, fetch = row["slices"][at], row["slices"][at + 1]
        assert ahead["ahead"] == 1 and ahead["tokens"] >= 1
        assert ahead["t1"] == fetch["t0"] and fetch["bytes"] == 4 * 5
        assert "uploads" in row["slices"][at - 2]
        assert "uploads" not in ahead
        body = [s for s in row["slices"] if s["name"] != "park"]
        for a, b in zip(body, body[1:]):
            assert a["t1"] == b["t0"]
        assert body[0]["t0"] == row["t0"] and body[-1]["t1"] == row["t1"]
        kinds = [p["phase"] for p in row["phases"]]
        assert "prefill_chunk" in kinds and "decode" in kinds
        for ph in row["phases"]:
            assert row["t0"] <= ph["t0"] <= ph["t1"] <= row["t1"]
    # A step that only met the chunk sent to it carries no chunk phase of
    # its own unless it sent the next one.
    for row in rows:
        chunks = [s for s in row["slices"] if s["name"] == "launch"
                  and s.get("program") == "prefill_chunk"]
        assert len(chunks) == sum(p["phase"] == "prefill_chunk"
                                  for p in row["phases"])


def test_enclose_opens_the_next_slices_annotation_round_the_one_between():
    """On the profiler's clock the decode's ``engine:fetch`` opens round
    the ``engine:launch`` of a chunk sent ahead; the row's slices tile."""
    from ray_tpu.serve.steplog import StepTimeline

    log = []

    class Ann:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            log.append(("enter", self.name, self.attrs))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    sl = StepTimeline(8)
    sl._annotate = Ann
    t0 = sl.step_begin()
    sl.begin("launch", program="decode")
    sl.begin("admit")
    sl.enclose("fetch", program="decode")
    sl.begin("launch", program="prefill_chunk", ahead=1)
    sl.begin("fetch", program="decode", bytes=20)
    sl.begin("sample_emit")
    sl.record(t0, time.time(), [], active=1, prefilling=1, queued=0)
    assert [e[:2] for e in log] == [
        ("enter", "engine:reap"), ("exit", "engine:reap"),
        ("enter", "engine:launch"), ("exit", "engine:launch"),
        ("enter", "engine:admit"), ("exit", "engine:admit"),
        ("enter", "engine:fetch"), ("enter", "engine:launch"),
        ("exit", "engine:launch"), ("exit", "engine:fetch"),
        ("enter", "engine:sample_emit"), ("exit", "engine:sample_emit"),
        ("enter", "engine:park")]
    assert log[7][2] == {"program": "prefill_chunk", "ahead": 1}
    row = sl.dump()["rows"][0]
    assert [s["name"] for s in row["slices"]] == [
        "reap", "launch", "admit", "launch", "fetch", "sample_emit"]
    assert row["slices"][4]["bytes"] == 20
    for a, b in zip(row["slices"], row["slices"][1:]):
        assert a["t1"] == b["t0"]
    # Asked for and not needed (the slice itself comes next), or another
    # slice comes where the enclosed one was to: nothing is left open.
    del log[:]
    sl.step_begin()
    sl.enclose("fetch")
    sl.begin("fetch")
    sl.enclose("fetch")
    sl.begin("launch")
    sl.begin("admit")
    assert [e[:2] for e in log] == [
        ("exit", "engine:park"), ("enter", "engine:reap"),
        ("exit", "engine:reap"), ("enter", "engine:fetch"),
        ("exit", "engine:fetch"), ("enter", "engine:fetch"),
        ("enter", "engine:launch"), ("exit", "engine:launch"),
        ("exit", "engine:fetch"), ("enter", "engine:admit")]


def test_in_a_trace_the_decodes_fetch_lies_round_a_launch_sent_ahead(
        tmp_path):
    """Read back with JAX's own reader: every ``engine:launch`` that says
    ``ahead`` lies inside an ``engine:fetch``, so the first fetch that
    STARTS after it is the one its run on the device ends under (what
    ``benchmarks/progtrace.py::launches`` brackets a run with)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    cfg, eng = _engine(prefill_chunk_tokens=32)
    warm = [eng.submit(p, max_new_tokens=8) for p in _prompts(cfg, [9, 100])]
    _drive(eng, warm)
    jax.profiler.start_trace(str(tmp_path))
    reqs = [eng.submit(p, max_new_tokens=8) for p in _prompts(cfg, [9, 100])]
    _drive(eng, reqs)
    jax.profiler.stop_trace()
    eng.shutdown()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name in ("engine:launch", "engine:fetch")]
    ahead = [e for e in events if e[0] == "engine:launch"
             and e[3].get("ahead")]
    fetches = sorted(e for e in events if e[0] == "engine:fetch")
    assert len(ahead) >= 2
    for _, a, b, stats in ahead:
        assert stats["program"] == "prefill_chunk"
        round_it = [f for f in fetches if f[1] <= a and b <= f[2]]
        assert len(round_it) == 1 and round_it[0][3]["program"] == "decode"
        # No fetch starts between this launch and the end of that one.
        assert not [f for f in fetches if a <= f[1] <= round_it[0][2]]


# ------------------------------------------------------------------ names


def test_engine_programs_are_jitted_under_their_keys_name():
    import jax
    import jax.numpy as jnp

    cfg, eng = _engine(prefill_chunk_tokens=32)
    state = jnp.asarray(eng._host_state())
    temps = jnp.zeros((4,), jnp.float32)
    bt = jnp.asarray(eng._block_tables)
    view = jnp.asarray(eng._live_view(eng._block_tables, eng._slot_pages))
    one = jnp.zeros((1,), jnp.int32)
    draw = (jnp.zeros((1,), jnp.float32), jnp.asarray(0, jnp.int32))
    lowered = {
        "decode": eng._decode.lower(eng.params, eng.cache, state, view,
                                    temps),
        "paged_prefill": eng._paged_prefill.lower(
            eng.params, eng.cache, jnp.zeros((1, 16), jnp.int32), one,
            bt[:1, :1], one, *draw, n=1, bucket=16),
        "paged_suffix": eng._paged_suffix.lower(
            eng.params, eng.cache, jnp.zeros((1, 16), jnp.int32), one, one,
            bt[:1, :2], one, *draw, n=1, bucket=16, width=2),
    }
    assert not hasattr(eng, "_decode_sampled")
    for key, low in lowered.items():
        head = low.as_text().split("\n", 1)[0]
        assert f"@jit_engine_{key} " in head, head
    # A program that ends in a sample returns the sample: on the program
    # text, no result of the three is as wide as the vocabulary (the
    # logits stay inside), and the decode's is ids + the step counter.
    for key, low in lowered.items():
        main = next(line for line in low.as_text().splitlines()
                    if "@main(" in line)
        results = re.findall(r"tensor<([\dx]*)x?[a-z]\w*>",
                             main.split("->", 1)[1])
        assert results, main
        for dims in results:
            assert str(cfg.vocab_size) not in dims.split("x"), (key, dims)
    assert [tuple(o.shape) for o in jax.tree.leaves(
        lowered["decode"].out_info)][0] == (4 + 1,)
    text = lowered["decode"].as_text(debug_info=True)
    for scope in ("paged_gather", "paged_attn"):
        assert re.search(rf'loc\("[^"]*{scope}', text), scope
    # The engine holds its weights in the compute dtype, so on its own
    # tree ``weight_cast`` names nothing; it still names the converts of a
    # program given float32 parameters.
    assert "weight_cast" not in text
    masters = _tiny()[1]
    text = eng._decode.lower(masters, eng.cache, state, view,
                             temps).as_text(debug_info=True)
    assert re.search(r'loc\("[^"]*weight_cast', text)
    text = lowered["paged_suffix"].as_text(debug_info=True)
    assert "paged_gather" in text and "paged_attn" in text
    eng.shutdown()


def test_flash_kernels_and_the_train_step_carry_their_names():
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    q = jnp.zeros((1, 128, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert re.search(rf'loc\("[^"]*{name}', text), name
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    step = ts.build_train_step(lambda p, b: (p["w"] * b["x"]).sum(),
                               optax.sgd(0.1), mesh)
    p = {"w": jnp.ones((4,))}
    low = step.lower(p, optax.sgd(0.1).init(p), {"x": jnp.ones((4,))})
    assert "@jit_train_step " in low.as_text().split("\n", 1)[0]


# ------------------------------------------------------------------- cost


def test_slices_cost_under_50_us_a_step():
    """Ten slices and a row, as a decode step with 32 active slots makes
    them, against the same row without slices: the best of five rounds,
    so a neighbour on the core does not decide it."""
    from ray_tpu.serve.steplog import StepTimeline

    phases = [{"phase": "decode", "t0": 0.0, "t1": 0.0, "batch": 32, "k": 1}]

    def step(sl, sliced):
        t0 = sl.step_begin() if sliced else time.time()
        if sliced:
            sl.begin("admit")
            sl.begin("pages")
            sl.begin("launch", program="decode", batch=32, ctx_tokens=9000)
            sl.begin("fetch", program="decode")
            sl.begin("sample_emit")
            sl.begin("finish")
            sl.begin("sample_emit")
        sl.record(t0, time.time(), phases, active=32, prefilling=0,
                  queued=0, pages_free=3, pages_pinned=80, ctx_tokens=9000)

    def per_step_us(sliced, n=3000):
        sl = StepTimeline(256)
        for _ in range(200):
            step(sl, sliced)
        best = float("inf")
        for _ in range(5):
            a = time.perf_counter()
            for _ in range(n):
                step(sl, sliced)
            best = min(best, (time.perf_counter() - a) / n * 1e6)
        return best

    cost = per_step_us(True) - per_step_us(False)
    assert cost < 50.0, f"slices cost {cost:.1f} us a step"
