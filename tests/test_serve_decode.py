"""Decode serving: continuous batching correctness + streaming generation
through the serve stack (VERDICT r4 Missing #2 / Next #3; reference:
replica call path ``serve/_private/replica.py:231`` + streaming
``proxy.py:761`` — here the engine owns the KV cache and jitted programs).
"""

import json
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


def _tiny(max_seq_len=128):
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64,
                            max_seq_len=max_seq_len)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


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
    return _tiny(max_seq_len=1024)


def _plain_engine(model, **kw):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    kw.setdefault("page_tokens", 16)
    kw.setdefault("capacity", 256)
    return DecodeEngine(params, cfg, slots=4, **kw)


@pytest.fixture
def serve_cluster(ray_start_regular):
    yield ray_start_regular
    try:
        serve.shutdown()
    except Exception:
        pass


def test_continuous_batching_matches_solo_generate():
    """Requests of different lengths decoded TOGETHER produce exactly what
    each produces alone (greedy): per-slot length masking is exact."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    prompts = [[5, 9, 2], [7], [11, 3, 4, 8, 1]]
    solo = [np.asarray(llama_decode.generate(
        params, np.array([p], np.int32), cfg, max_new_tokens=6))[0]
        for p in prompts]

    eng = DecodeEngine(params, cfg, slots=4, capacity=64)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(40):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    for req, want in zip(reqs, solo):
        assert req.output == list(want), (req.output, list(want))


def test_request_joins_mid_stream():
    """A request submitted while another is mid-decode joins the running
    batch (continuous batching) and still matches its solo output."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64)
    first = eng.submit([3, 1, 4], max_new_tokens=10)
    for _ in range(4):
        eng.step()
    assert not first.done.is_set()
    late = eng.submit([9, 9], max_new_tokens=4)
    for _ in range(30):
        if first.done.is_set() and late.done.is_set():
            break
        eng.step()
    solo_first = np.asarray(llama_decode.generate(
        params, np.array([[3, 1, 4]], np.int32), cfg,
        max_new_tokens=10))[0]
    solo_late = np.asarray(llama_decode.generate(
        params, np.array([[9, 9]], np.int32), cfg, max_new_tokens=4))[0]
    assert first.output == list(solo_first)
    assert late.output == list(solo_late)
    # Slots recycled.
    assert eng.stats()["free_slots"] == 2


def test_more_requests_than_slots_queue_and_finish():
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64)
    reqs = [eng.submit([i + 1], max_new_tokens=3) for i in range(5)]
    for _ in range(60):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(len(r.output) == 3 for r in reqs)


@pytest.mark.timeout_s(240)
def test_streaming_generation_through_serve(serve_cluster):
    """Tokens stream through the per-node proxy as the engine emits them:
    deployment -> replica stream session -> HTTP chunked response."""
    import urllib.request

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    serve.run(
        serve.deployment(LlamaDecodeDeployment).options(
            max_concurrency=4).bind(config=cfg, slots=2, capacity=64),
        name="llm")
    handle = serve.get_deployment_handle("llm")

    # Unary path: full generation in one reply (+ TTFT measured).
    out = handle.remote({"tokens": [5, 9, 2],
                         "max_new_tokens": 5}).result(timeout=120)
    assert len(out["tokens"]) == 5
    assert out["ttft_s"] >= 0

    # Handle streaming path.
    toks = list(handle.stream({"tokens": [5, 9, 2], "max_new_tokens": 5,
                               "stream": True}))
    assert toks == out["tokens"]  # greedy == deterministic

    # HTTP chunked streaming through the per-node proxy.
    host, port = serve.start_http()
    req = urllib.request.Request(
        f"http://{host}:{port}/llm",
        data=json.dumps({"tokens": [5, 9, 2], "max_new_tokens": 5,
                         "stream": True}).encode(),
        headers={"X-Serve-Stream": "1"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        lines = [json.loads(ln) for ln in resp.read().splitlines() if ln]
    assert lines == out["tokens"]


def test_slot_reuse_after_eos_has_no_stale_kv():
    """A request that ends at its EOS leaves K/V in its pages and a
    cursor that ``_finish`` resets. A request re-admitted into that slot
    must see none of it: prefill overwrites its positions and the
    length mask hides the rest."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=1, capacity=64,
                       prefix_pool_entries=0)
    first_prompt = [3, 1, 4]
    solo_first = np.asarray(llama_decode.generate(
        params, np.array([first_prompt], np.int32), cfg,
        max_new_tokens=9))[0]
    eos = int(solo_first[2])  # EOS lands in a decode step, mid-stream
    r1 = eng.submit(first_prompt, max_new_tokens=9, eos_id=eos)
    _drive(eng, [r1], budget=20)
    assert r1.output[-1] == eos
    assert len(r1.output) < 9  # actually truncated mid-stream
    # Re-admit into the SAME slot (slots=1): longer than the first
    # request so its decode walks through the stale positions.
    second_prompt = [9, 9, 2, 7]
    r2 = eng.submit(second_prompt, max_new_tokens=12)
    _drive(eng, [r2], budget=40)
    assert r2.slot == r1.slot
    solo_second = np.asarray(llama_decode.generate(
        params, np.array([second_prompt], np.int32), cfg,
        max_new_tokens=12))[0]
    assert r2.output == list(solo_second), (r2.output, list(solo_second))
    eng.shutdown()


def test_admission_wave_pad_rows_idempotent():
    """_admit pads a non-power-of-two admission wave by repeating the
    last real row into the SAME slot: the duplicate prefill must be an
    idempotent overwrite (no fourth slot consumed, last request exact)."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=4, capacity=64,
                       prefix_pool_entries=0)
    prompts = [[5, 9, 2], [7, 1], [11, 3, 4]]  # wave of 3 -> n=4 padded
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()  # single admission wave
    assert eng.stats()["free_slots"] == 1  # pad row consumed NO slot
    for _ in range(40):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    for req, p in zip(reqs, prompts):
        solo = np.asarray(llama_decode.generate(
            params, np.array([p], np.int32), cfg, max_new_tokens=6))[0]
        assert req.output == list(solo), (req.output, list(solo))
    assert eng.stats()["free_slots"] == 4
    eng.shutdown()


def test_on_token_failure_recorded_not_swallowed():
    """A broken streaming callback must not kill the decode loop, but
    the failure must be diagnosable: recorded on the request and logged
    once (rate-limited) instead of silently passed."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64,
                       prefix_pool_entries=0)
    seen = []

    def bad(tok):
        seen.append(tok)
        raise RuntimeError("consumer wedged")

    broken = eng.submit([5, 9, 2], max_new_tokens=4, on_token=bad)
    healthy = eng.submit([7, 1], max_new_tokens=4)
    for _ in range(20):
        if broken.done.is_set() and healthy.done.is_set():
            break
        eng.step()
    assert broken.done.is_set() and len(broken.output) == 4
    assert broken.on_token_error is not None
    assert "consumer wedged" in broken.on_token_error
    assert len(seen) == 4  # every token still offered to the callback
    assert healthy.on_token_error is None
    assert len(healthy.output) == 4
    eng.shutdown()


@pytest.mark.timeout_s(240)
def test_prefix_residency_published_to_router(serve_cluster):
    """Replica prefix residency flows replica_metrics -> ReplicaActor
    .stats -> controller snapshot -> router, where prefix-affinity
    routing reads it; replica load (decode backlog) reaches the
    controller's status the same way."""
    from ray_tpu.models import llama
    from ray_tpu.serve.decode import LlamaDecodeDeployment
    from ray_tpu.serve.deployment import _Router

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    serve.run(
        serve.deployment(LlamaDecodeDeployment).options(
            max_concurrency=4).bind(config=cfg, slots=2, capacity=64,
                                    kv_page_tokens=16,
                                    prefix_pool_entries=4,
                                    prefix_match_min_tokens=4),
        name="llm_prefix")
    handle = serve.get_deployment_handle("llm_prefix")
    prompt = list(range(1, 25))  # long enough to insert a pool entry
    out = handle.remote({"tokens": prompt,
                         "max_new_tokens": 2}).result(timeout=120)
    assert len(out["tokens"]) == 2
    # The reconcile loop picks up the new residency and republishes;
    # the router's snapshot eventually advertises the prefix.
    router = _Router.get("llm_prefix")
    deadline = time.monotonic() + 60
    advertised = set()
    while time.monotonic() < deadline:
        with router._lock:
            advertised = set().union(*(r["prefixes"]
                                       for r in router._replicas)) \
                if router._replicas else set()
        if advertised:
            break
        time.sleep(0.25)
    assert advertised, "prefix residency never reached the router"
    status = serve.status()["llm_prefix"]
    assert "load" in status


def test_submit_rejects_over_capacity_budget():
    """ADVICE medium: a request whose prompt + max_new_tokens exceeds the
    cache capacity must be rejected at submit — past capacity the K/V
    scatter silently drops writes and the engine would return wrong
    tokens instead of an error."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=32, page_tokens=16)
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 26)), max_new_tokens=10)  # 25 + 10 > 32
    # Exactly at the budget is admitted and completes.
    req = eng.submit([1, 2, 3], max_new_tokens=29)  # 3 + 29 == 32
    for _ in range(60):
        if req.done.is_set():
            break
        eng.step()
    assert req.done.is_set()
    assert len(req.output) == 29


def test_every_compile_routes_through_dispatch_fresh():
    """Regression (lint-pinned by graftlint donation-unguarded-
    dispatch): every program the engine compiles is marked once through
    _dispatch_fresh, and a same-bucket request causes no new compile —
    neither a new program key nor an XLA compile request (the check
    chip_smoke.py makes on the chip)."""
    from ray_tpu.serve import decode as decode_mod

    cfg, params = _tiny()
    eng = decode_mod.DecodeEngine(params, cfg, slots=2, capacity=64)
    req = eng.submit([5, 9, 2], max_new_tokens=4)
    for _ in range(30):
        if req.done.is_set():
            break
        eng.step()
    assert req.done.is_set()
    assert eng._compiled
    keys = set(eng._compiled)
    compiles = eng.device_stats()["compiles"]
    # a same-bucket request re-dispatches every program: no new
    # program keys, no new compile requests
    req2 = eng.submit([7, 1, 3], max_new_tokens=4)
    for _ in range(30):
        if req2.done.is_set():
            break
        eng.step()
    assert req2.done.is_set()
    assert set(eng._compiled) == keys
    assert eng.device_stats()["compiles"] == compiles
    eng.shutdown()


# ------------------------------------------------------------ one path


@pytest.mark.parametrize("name", ["llama", "deepseek", "mimo", "phi4flash"])
def test_every_step_of_every_served_model_takes_the_one_path(name):
    """Greedy and sampled requests, a chunked prefill, more requests than
    slots and a pool that forces a preemption, at the model's debug
    preset: whatever reaches ``_dispatch_fresh`` is a prefill program or
    ``("decode", rung)``, and a step that advanced slots dispatched
    exactly one decode, on a rung of the ladder."""
    import importlib

    import jax

    from ray_tpu.serve.decode import DecodeEngine

    mod = importlib.import_module(f"ray_tpu.models.{name}")
    dec = importlib.import_module(f"ray_tpu.models.{name}_decode")
    cfg = mod.PRESETS["debug"]
    eng = DecodeEngine(mod.init_params(cfg, jax.random.key(0)), cfg,
                       slots=3, capacity=64, prefill_bucket=8, page_tokens=4,
                       pool_pages=7, prefill_chunk_tokens=8,
                       prefix_pool_entries=0, model=dec)
    keys = []
    dispatch = eng._dispatch_fresh

    def spy(key, call, *args, **kwargs):
        keys.append(key)
        return dispatch(key, call, *args, **kwargs)

    eng._dispatch_fresh = spy
    rng = np.random.default_rng(31)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                       max_new_tokens=new, temperature=temp)
            for n, new, temp in ((8, 8, 0.0), (8, 9, 0.8), (12, 4, 0.0),
                                 (10, 6, 0.6), (9, 5, 0.0))]
    for _ in range(600):
        if all(r.done.is_set() for r in reqs):
            break
        seen = len(keys)
        stepped = eng.step()
        mine = keys[seen:]
        assert {k[0] for k in mine} <= {"decode", "paged_prefill",
                                        "paged_suffix"}, mine
        decodes = [k for k in mine if k[0] == "decode"]
        assert len(decodes) == (stepped > 0), (stepped, mine)
        assert all(len(k) == 2 and k[1] in eng._view_ladder
                   for k in decodes), decodes
    assert all(r.status == "completed" for r in reqs)
    assert [len(r.output) for r in reqs] == [8, 9, 4, 6, 5]
    assert eng.preempted >= 1 and eng.prefill_chunks >= 3
    assert eng.steps == sum(k[0] == "decode" for k in keys)
    assert eng.stats()["pages_in_use"] == 0
    eng.shutdown()


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
