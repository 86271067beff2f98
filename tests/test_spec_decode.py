"""Speculative decoding engine mode + step-loop constants (ISSUE 16).

The contract under test: with a draft model attached (``spec_k > 0``)
the engine emits GREEDY streams bit-identical (np.array_equal, no
tolerance) to the non-speculative path — the verify forward makes
acceptance provable, so draft quality only moves THROUGHPUT, never
tokens. Covered here: spec-vs-plain exactness across page/bucket
boundaries, perfect-draft step compression, mesh-sharded spec replicas,
rejection-rollback page accounting under a randomized soak with
cancels/deadlines mid-round, composition with prefix-cache and chunked
prefill, the draftless/mixed-temperature fallbacks with draft resync,
the fused device sampler's greedy parity, and warmup pre-dispatch. All
CPU, tiny configs — tier-1 safe."""

import numpy as np
import pytest


def _tiny(max_seq_len=1024):
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64,
                            max_seq_len=max_seq_len)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _tiny_draft(cfg):
    """A genuinely smaller draft over the SAME vocab: proposals are
    frequently wrong, so acceptance, rejection and rollback all
    exercise for real."""
    import jax

    from ray_tpu.models import llama

    dcfg = llama.LlamaConfig(vocab_size=cfg.vocab_size, dim=16,
                             n_layers=1, n_heads=2, n_kv_heads=1,
                             mlp_dim=32, max_seq_len=cfg.max_seq_len)
    return dcfg, llama.init_params(dcfg, jax.random.key(1))


def _drive(eng, reqs, budget=600):
    for _ in range(budget):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError(
        f"requests not done in {budget} steps: "
        f"{[r.status for r in reqs]}")


def _outputs(eng, prompts, n_tok, **submit_kw):
    reqs = [eng.submit(p, max_new_tokens=n_tok, **submit_kw)
            for p in prompts]
    _drive(eng, reqs)
    return [np.asarray(r.output, np.int32) for r in reqs]


@pytest.fixture(scope="module")
def model():
    return _tiny()


@pytest.fixture(scope="module")
def draft(model):
    return _tiny_draft(model[0])


def _spec_engine(model, draft, k=4, **kw):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    dcfg, dparams = draft
    kw.setdefault("page_tokens", 16)
    kw.setdefault("capacity", 256)
    return DecodeEngine(params, cfg, slots=4,
                        spec_draft_params=dparams,
                        spec_draft_config=dcfg, spec_k=k, **kw)


def _plain_engine(model, **kw):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    kw.setdefault("page_tokens", 16)
    kw.setdefault("capacity", 256)
    return DecodeEngine(params, cfg, slots=4, **kw)


# ----------------------------------------------------- greedy exactness


def test_spec_greedy_bit_exact_across_boundaries(model, draft):
    """Spec output == plain output, np.array_equal, with prompts and
    generation lengths chosen to cross page (16) and suffix-bucket
    boundaries mid-round: 15+18 straddles a page edge inside one
    accepted run, 30+24 crosses two."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 60, size=n).tolist()
               for n in (5, 15, 17, 30)]
    plain = _plain_engine(model)
    want = _outputs(plain, prompts, 24)
    plain.shutdown()
    spec = _spec_engine(model, draft, k=4)
    got = _outputs(spec, prompts, 24)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    s = spec.stats()["spec"]
    assert s["rounds"] > 0 and s["proposed_tokens"] > 0
    # every step either emitted or fell back — never lost a token
    assert spec.tokens_out == sum(len(w) for w in want)
    spec.shutdown()


@pytest.mark.slow  # PR 20 rebudget (7.8s): step-compression property;
# accept-rate plumbing and bit-exactness keep their own tier-1 gates
def test_spec_perfect_draft_compresses_steps(model):
    """Draft == target => every proposal accepted (rate 1.0) and the
    target runs ~1/(k+1) as many forwards: the acceptance math, length
    bookkeeping and multi-token emission all land in one assert."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 60, size=n).tolist()
               for n in (6, 13, 21, 34)]
    plain = _plain_engine(model)
    want = _outputs(plain, prompts, 24)
    base_steps = plain.steps
    plain.shutdown()
    spec = _spec_engine(model, (cfg, params), k=4)
    got = _outputs(spec, prompts, 24)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    s = spec.stats()["spec"]
    assert s["accept_rate"] == 1.0
    assert spec.steps * 3 < base_steps
    spec.shutdown()


@pytest.mark.slow  # PR 20 rebudget (6.9s): truncation edge case;
# boundary bit-exactness stays tier-1
def test_spec_eos_and_max_tokens_truncate_mid_round(model):
    """EOS landing inside an accepted run must cut the stream exactly
    where sequential decode would: drive plain first to learn a token
    that appears mid-stream, then replay both engines with it as
    eos_id."""
    cfg, params = model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 60, size=n).tolist() for n in (8, 19)]
    probe = _plain_engine(model)
    ref = _outputs(probe, prompts, 20)
    probe.shutdown()
    eos = int(ref[0][4])  # 5th token of stream 0 = a mid-round EOS
    plain = _plain_engine(model)
    want = _outputs(plain, prompts, 20, eos_id=eos)
    plain.shutdown()
    spec = _spec_engine(model, (cfg, params), k=4)  # perfect draft:
    #   the accepted run is guaranteed to span the EOS position
    got = _outputs(spec, prompts, 20, eos_id=eos)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    assert got[0][-1] == eos and len(got[0]) <= 5
    spec.shutdown()


MESHES = [
    # One shape stays in tier-1 (the full-model-axis one); the other
    # two re-trace the same programs under different divisibility
    # splits and ride the slow lane (tier-1 budget).
    pytest.param((1, 8), marks=pytest.mark.slow),   # 8.6s: re-trace only
    pytest.param((2, 4), marks=pytest.mark.slow),   # 4.9s: re-trace only
    (8, 1),
]


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_spec_mesh_sharded_bit_exact(mesh_shape):
    """Spec mode on a GSPMD decode mesh == single-chip plain decode,
    np.array_equal: the verify/draft programs trace under the decode
    axis rules (draft under its OWN divisibility specialization), so
    sharding moves bytes, never logits."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    cfg = llama.LlamaConfig(vocab_size=64, dim=32, n_layers=2,
                            n_heads=8, n_kv_heads=8, mlp_dim=64,
                            max_seq_len=256)
    params = llama.init_params(cfg, jax.random.key(0))
    dcfg = llama.LlamaConfig(vocab_size=64, dim=16, n_layers=1,
                             n_heads=8, n_kv_heads=8, mlp_dim=32,
                             max_seq_len=256)
    dparams = llama.init_params(dcfg, jax.random.key(1))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 60, size=n).tolist() for n in (7, 18)]
    plain = DecodeEngine(params, cfg, slots=8, capacity=128,
                         page_tokens=16)
    want = _outputs(plain, prompts, 16)
    plain.shutdown()
    spec = DecodeEngine(params, cfg, slots=8, capacity=128,
                        page_tokens=16, mesh_shape=mesh_shape,
                        spec_draft_params=dparams,
                        spec_draft_config=dcfg, spec_k=3)
    got = _outputs(spec, prompts, 16)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    assert spec.stats()["spec"]["rounds"] > 0
    spec.shutdown()


# ------------------------------------------------- rollback accounting


def test_spec_rollback_soak_zero_leaked_pages(model, draft):
    """200+ randomized steps against a REAL (wrong-often) draft:
    admissions, cancels and deadlines land mid-round, rejected tails
    roll page cursors back every few rounds, the overcommitted pools
    preempt. Terminal invariants: both allocators drain to exactly the
    prefix pins (target) and zero (draft), and un-shared completions
    are token-exact vs plain."""
    cfg, params = model
    dcfg, dparams = draft
    from ray_tpu.serve.decode import DecodeEngine

    rng = np.random.default_rng(42)
    eng = DecodeEngine(params, cfg, slots=4, capacity=256,
                       page_tokens=16, pool_pages=48,
                       spec_draft_params=dparams, spec_draft_config=dcfg,
                       spec_k=4, spec_draft_pool_pages=40,
                       prefix_pool_entries=4, prefix_match_min_tokens=16)
    plain = _plain_engine(model)
    live, done, submitted = [], [], 0
    for _ in range(240):
        if submitted < 20 and rng.random() < 0.3 and len(live) < 8:
            prompt = rng.integers(
                1, 60, size=int(rng.integers(3, 60))).tolist()
            n = int(rng.integers(1, 28))
            dl = (0.02 if rng.random() < 0.08 else None)  # expires
            #   mid-flight, usually inside a spec round
            live.append([eng.submit(prompt, max_new_tokens=n,
                                    deadline_s=dl), prompt, n, False])
            submitted += 1
        if live and rng.random() < 0.06:
            victim = live[int(rng.integers(len(live)))]
            if not victim[3]:
                eng.cancel(victim[0].request_id)
                victim[3] = True
        eng.step()
        for e in list(live):
            if e[0].done.is_set():
                live.remove(e)
                done.append(e)
    for _ in range(2000):
        if all(e[0].done.is_set() for e in live):
            break
        eng.step()
    done += live
    assert all(e[0].done.is_set() for e in done)
    exact = 0
    for req, prompt, n, cancelled in done:
        if req.status != "completed":
            continue
        if req.prompt_len == len(prompt) and req.prefix_len == 0:
            [want] = _outputs(plain, [prompt], n)
            assert np.array_equal(want,
                                  np.asarray(req.output, np.int32))
            exact += 1
    assert exact >= 5
    s = eng.stats()
    assert s["pages_in_use"] == s["pages_pinned"], "leaked target pages"
    assert s["spec"]["draft_pages_free"] \
        == s["spec"]["draft_pages_total"], "leaked draft pages"
    assert s["spec"]["rounds"] > 20
    assert 0 < s["spec"]["accepted_tokens"] \
        < s["spec"]["proposed_tokens"], \
        "soak must see both acceptance and rejection"
    plain.shutdown()
    eng.shutdown()


# ----------------------------------------------------------- composition


def test_spec_composes_with_prefix_cache(model, draft):
    """Second submission of a shared prompt splices cached pages into
    the TARGET while the draft re-prefills (it has no prefix index) —
    outputs stay bit-exact and the hit really happened."""
    rng = np.random.default_rng(9)
    shared = rng.integers(1, 60, size=48).tolist()
    prompts = [shared + [7], shared + [11]]
    plain = _plain_engine(model, prefix_pool_entries=4,
                          prefix_match_min_tokens=16)
    want = [_outputs(plain, [p], 16)[0] for p in prompts]
    plain.shutdown()
    spec = _spec_engine(model, draft, k=3, prefix_pool_entries=4,
                        prefix_match_min_tokens=16)
    got = [_outputs(spec, [p], 16)[0] for p in prompts]
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    assert spec.stats()["prefix"]["hits"] >= 1
    spec.shutdown()


def test_spec_composes_with_chunked_prefill(model, draft):
    """A long prompt admits through chunked prefill WHILE a short one
    decodes speculatively: spec rounds run with a mid-prefill slot in
    the batch (its verify row is junk routed to scratch/overwritten
    positions) and both streams match the plain chunked engine."""
    rng = np.random.default_rng(13)
    long_p = rng.integers(1, 60, size=150).tolist()
    short_p = rng.integers(1, 60, size=6).tolist()

    def run(eng):
        r_short = eng.submit(short_p, max_new_tokens=24)
        r_long = eng.submit(long_p, max_new_tokens=12)
        _drive(eng, [r_short, r_long])
        return (np.asarray(r_short.output, np.int32),
                np.asarray(r_long.output, np.int32))

    plain = _plain_engine(model, prefill_chunk_tokens=32, capacity=512)
    want = run(plain)
    plain.shutdown()
    spec = _spec_engine(model, draft, k=4, prefill_chunk_tokens=32,
                        capacity=512)
    got = run(spec)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    assert spec.prefill_chunks > 0, "chunked path really ran"
    assert spec.stats()["spec"]["rounds"] > 0
    spec.shutdown()


def test_spec_mixed_temperature_falls_back_and_resyncs(model, draft):
    """A sampled request in the batch parks spec on the plain path (the
    acceptance rule is argmax-only); when it finishes, spec resumes on
    slots whose drafts fell arbitrarily behind — the resync prefill
    rebuilds them and the greedy stream stays bit-exact end to end."""
    cfg, params = model
    rng = np.random.default_rng(17)
    greedy_p = rng.integers(1, 60, size=9).tolist()
    sampled_p = rng.integers(1, 60, size=5).tolist()
    plain = _plain_engine(model)
    [want] = _outputs(plain, [greedy_p], 40)
    plain.shutdown()
    spec = _spec_engine(model, draft, k=3)
    r_g = spec.submit(greedy_p, max_new_tokens=40)
    r_s = spec.submit(sampled_p, max_new_tokens=6, temperature=0.9)
    _drive(spec, [r_g, r_s])
    assert np.array_equal(want, np.asarray(r_g.output, np.int32))
    assert spec.stats()["spec"]["rounds"] > 0, \
        "spec must resume after the sampled request drains"
    spec.shutdown()


def test_spec_draftless_fallback_stays_exact(model, draft):
    """A draft pool too small to seat anything demotes slots to
    draftless (junk proposals, all rejected): output identical, zero
    acceptance bookkeeping, no leak."""
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, 60, size=n).tolist() for n in (40, 50)]
    plain = _plain_engine(model)
    want = _outputs(plain, prompts, 12)
    plain.shutdown()
    spec = _spec_engine(model, draft, k=3, spec_draft_pool_pages=2)
    got = _outputs(spec, prompts, 12)
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    s = spec.stats()["spec"]
    assert s["proposed_tokens"] == 0, \
        "draftless slots must not pollute acceptance metrics"
    assert s["draft_pages_free"] == s["draft_pages_total"]
    spec.shutdown()


# -------------------------------------------------- device-side sampler


@pytest.mark.parametrize("page_tokens", [16, 64])
def test_device_sampler_greedy_parity(model, page_tokens):
    """The ids a program returns are the argmax of the model's own
    logits, first maximum first: each engine dispatch is held to
    ``paged_prefill`` / ``paged_decode_step`` called directly on the
    same arguments and ``np.argmax``-ed on the host, at a small page and
    at the default one; the streams are the plain reference's."""
    import jax

    from ray_tpu.models import llama_decode as ld
    from ray_tpu.serve.decode import _pool_of
    from tests.stream_reference import assert_stream_is_the_references

    cfg, params = model
    eng = _plain_engine(model, page_tokens=page_tokens)
    direct_prefill = jax.jit(ld.paged_prefill, static_argnums=(4,))
    direct_step = jax.jit(ld.paged_decode_step, static_argnums=(5,))
    checked = {"prefill": 0, "decode": 0}
    prefill, decode = eng._paged_prefill, eng._decode

    def spied_prefill(p, cache, rows, lengths, bt, slot_ids, temps, wave,
                      n, bucket):
        want = np.asarray(direct_prefill(
            p, rows[:, :bucket], _pool_of(cache), bt, cfg,
            lengths=lengths)[0]).argmax(-1)
        ids, cache = prefill(p, cache, rows, lengths, bt, slot_ids, temps,
                             wave, n=n, bucket=bucket)
        assert np.array_equal(np.asarray(ids), want)
        checked["prefill"] += 1
        return ids, cache

    def spied_decode(p, cache, state, view, temps):
        want = np.asarray(direct_step(
            p, _pool_of(cache), view, cache["length"], state[:eng.slots],
            cfg)[0]).argmax(-1)
        out, cache = decode(p, cache, state, view, temps)
        assert np.array_equal(np.asarray(out)[:eng.slots], want)
        checked["decode"] += 1
        return out, cache

    eng._paged_prefill, eng._decode = spied_prefill, spied_decode
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 60, size=n).tolist() for n in (4, 12, 27)]
    got = _outputs(eng, prompts, 18)
    assert checked["prefill"] >= 1 and checked["decode"] >= 17
    for prompt, served in zip(prompts, got):
        assert len(served) == 18
        assert_stream_is_the_references(params, cfg, prompt, served)
    eng.shutdown()


def test_device_sampler_sampled_rows_deterministic(model):
    """Rows with a temperature draw on the programs' counter-based
    streams: deterministic (two identical engines agree token for
    token), and nothing of numpy's."""
    rng = np.random.default_rng(29)
    prompts = [rng.integers(1, 60, size=8).tolist()]
    outs = []
    for _ in range(2):
        eng = _plain_engine(model)
        assert not hasattr(eng, "_rng")
        outs.append(_outputs(eng, prompts, 12,
                             temperature=0.8)[0])
        eng.shutdown()
    assert np.array_equal(outs[0], outs[1])
    assert all(0 <= t < _tiny()[0].vocab_size for t in outs[0])


def test_sample_batch_draws_from_the_softmax_at_its_temperature():
    """4,000 draws of one 16-way row at T = 0.7 follow
    ``softmax(logits / T)`` (chi-square, 15 degrees of freedom: 37.7 is
    the 0.1% point), and a temperature row among greedy rows leaves the
    greedy rows what they were."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama_decode import sample_batch

    rng = np.random.default_rng(41)
    row = rng.normal(size=16).astype(np.float32)
    n, temp = 4000, 0.7
    draws = np.asarray(sample_batch(
        jnp.broadcast_to(row, (n, 16)), jnp.full((n,), temp, jnp.float32),
        jax.random.key(5)))
    p = np.exp((row - row.max()) / temp)
    p /= p.sum()
    seen = np.bincount(draws, minlength=16)
    assert seen.sum() == n and (n * p).min() > 5
    chi2 = float((((seen - n * p) ** 2) / (n * p)).sum())
    assert chi2 < 37.7, (chi2, seen.tolist())
    # ...and not the untempered softmax: the statistic tells them apart.
    p1 = np.exp(row - row.max())
    p1 /= p1.sum()
    assert float((((seen - n * p1) ** 2) / (n * p1)).sum()) > 37.7
    logits = jnp.asarray(rng.normal(size=(6, 16)).astype(np.float32))
    logits = logits.at[2, 3].set(logits[2].max())  # a tie: the first wins
    greedy = np.asarray(sample_batch(
        logits, jnp.zeros((6,), jnp.float32), jax.random.key(0)))
    assert np.array_equal(greedy, np.asarray(logits).argmax(-1))
    for seed in range(8):
        mixed = np.asarray(sample_batch(
            logits, jnp.asarray([0, 0, 0, 5.0, 0, 0], jnp.float32),
            jax.random.key(seed)))
        assert np.array_equal(np.delete(mixed, 3), np.delete(greedy, 3))


def test_a_temperature_compiles_nothing_in_a_warmed_engine(model):
    """The temperatures are an argument of the programs: a request with
    one, admitted into an engine that served greedy requests, adds no
    program key and no compile."""
    from ray_tpu.util.compile_cache import compile_watch

    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, 60, size=9).tolist() for _ in range(2)]
    eng = _plain_engine(model)
    eng.warm_decode()
    _outputs(eng, prompts, 6)
    keys = set(eng._compiled)
    compiles = compile_watch().snapshot()["compiles"]
    got = _outputs(eng, prompts, 6, temperature=0.9)
    assert set(eng._compiled) == keys
    assert compile_watch().snapshot()["compiles"] == compiles
    assert all(len(g) == 6 for g in got)
    eng.shutdown()


@pytest.mark.slow  # PR 20 rebudget (8.2s): warmup perf property
def test_warmup_predispatches_step_programs(model, draft):
    """warmup() compiles the step-loop grid before traffic: the compile
    keys are marked, the parked KV lengths come back zeroed, and the
    first real requests emit the exact greedy streams."""
    import numpy as _np

    spec = _spec_engine(model, draft, k=3, decode_chunk=4)
    spec.warmup()
    # 4 slots x 16 pages: the ladder of view widths is the one rung 64.
    for key in [("decode", 64), ("decode_k", 2, 64),
                ("decode_k", 4, 64), ("spec_draft", 3, 64),
                ("spec_verify", 3), ("paged_prefill", 1, 128)]:
        assert key in spec._compiled, key
    assert _np.asarray(spec.cache["length"]).sum() == 0
    assert _np.asarray(spec._draft_cache["length"]).sum() == 0
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 60, size=10).tolist()]
    plain = _plain_engine(model)
    want = _outputs(plain, prompts, 10)
    plain.shutdown()
    got = _outputs(spec, prompts, 10)
    assert np.array_equal(want[0], got[0])
    spec.shutdown()


# --------------------------------------------------------- observability


@pytest.mark.slow  # PR 20 rebudget (6.2s): stats/steplog plumbing;
# spec correctness and accept-rate math keep their fast gates
def test_spec_stats_steplog_and_deployment_plumbing(model, draft):
    """spec stats() block, draft/verify steplog phases, timeline() spec
    flag, and the deployment-level replica_metrics passthrough."""
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    spec = _spec_engine(model, draft, k=3, step_timeline=128)
    rng = np.random.default_rng(37)
    _outputs(spec, [rng.integers(1, 60, size=9).tolist()], 12)
    s = spec.stats()["spec"]
    for key in ("k", "rounds", "proposed_tokens", "accepted_tokens",
                "accept_rate", "draft_pages_total", "draft_pages_free"):
        assert key in s
    assert s["k"] == 3 and s["rounds"] > 0
    tl = spec.timeline()
    assert tl["spec_k"] == 3
    names = [p["phase"] for row in tl["rows"] for p in row["phases"]]
    assert "draft" in names and "verify" in names
    vp = [p for row in tl["rows"] for p in row["phases"]
          if p["phase"] == "verify"]
    assert all("accepted" in p and p["k"] == 3 for p in vp)
    spec.shutdown()

    dep = LlamaDecodeDeployment.__new__(LlamaDecodeDeployment)
    dep.engine = _spec_engine(model, draft, k=3)
    _outputs(dep.engine, [rng.integers(1, 60, size=7).tolist()], 8)
    rm = dep.replica_metrics()
    assert rm["spec"]["rounds"] > 0
    dep.engine.shutdown()


def test_spec_terminal_metrics_observed(model, draft):
    """Per-request spec counters/histogram land at the terminal step
    through serve.metrics and aggregate into slo_summary."""
    import uuid

    from ray_tpu.serve import metrics as smetrics
    from ray_tpu.util.metrics import _Registry

    dep = f"specdep-{uuid.uuid4().hex[:6]}"
    spec = _spec_engine(model, draft, k=3, metrics_enabled=True,
                        metrics_deployment=dep)
    rng = np.random.default_rng(41)
    _outputs(spec, [rng.integers(1, 60, size=11).tolist()], 12)
    spec.shutdown()
    summary = smetrics.slo_summary(
        {"local": _Registry.get().snapshot()})
    rec = summary.get(dep, {})
    assert rec.get("spec_proposed_tokens", 0) > 0
    assert 0 <= rec.get("spec_accepted_tokens", 0) \
        <= rec["spec_proposed_tokens"]
    assert rec["spec_accept_rate"]["count"] >= 1


def test_spec_off_path_unchanged(model):
    """spec OFF = byte-identical legacy behavior: no draft structures,
    no spec stats key, plain step loop."""
    eng = _plain_engine(model)
    assert eng.spec is False
    assert "spec" not in eng.stats()
    assert not hasattr(eng, "_draft_pages") or not eng.spec
    rng = np.random.default_rng(43)
    _outputs(eng, [rng.integers(1, 60, size=6).tolist()], 6)
    assert "spec" not in eng.stats()
    eng.shutdown()
