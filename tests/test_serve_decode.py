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


def _tiny():
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


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


def test_chunked_decode_matches_per_token():
    """decode_chunk>1 (K greedy steps per device call) produces exactly
    the per-token stream, including eos truncation and mid-stream joins
    falling back to per-token steps."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    prompts = [[5, 9, 2], [7, 1], [11, 3, 4]]
    solo = [np.asarray(llama_decode.generate(
        params, np.array([p], np.int32), cfg, max_new_tokens=9))[0]
        for p in prompts]
    eng = DecodeEngine(params, cfg, slots=4, capacity=64, decode_chunk=4)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    for _ in range(40):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    for req, want in zip(reqs, solo):
        assert req.output == list(want), (req.output, list(want))
    # eos truncation inside a chunk
    eos = int(solo[0][3])
    req = eng.submit(prompts[0], max_new_tokens=9, eos_id=eos)
    for _ in range(20):
        if req.done.is_set():
            break
        eng.step()
    assert req.output[-1] == eos
    assert len(req.output) <= 4 + 3  # truncated at/before the eos chunk


def test_slot_reuse_after_mid_chunk_eos_has_no_stale_kv():
    """Chunked decode writes K/V for the remaining chunk steps PAST a
    request's EOS before _finish resets the slot's length. A request
    re-admitted into that slot must see none of the stale K/V: prefill
    overwrites its positions and the length mask hides the rest."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=1, capacity=64, decode_chunk=4,
                       prefix_pool_entries=0)
    first_prompt = [3, 1, 4]
    solo_first = np.asarray(llama_decode.generate(
        params, np.array([first_prompt], np.int32), cfg,
        max_new_tokens=9))[0]
    eos = int(solo_first[2])  # EOS lands mid-chunk (chunk of 4, idx 2)
    r1 = eng.submit(first_prompt, max_new_tokens=9, eos_id=eos)
    for _ in range(20):
        if r1.done.is_set():
            break
        eng.step()
    assert r1.done.is_set() and r1.output[-1] == eos
    assert len(r1.output) < 9  # actually truncated mid-stream
    # Re-admit into the SAME slot (slots=1): longer than the first
    # request so its decode walks through the stale positions.
    second_prompt = [9, 9, 2, 7]
    r2 = eng.submit(second_prompt, max_new_tokens=12)
    for _ in range(40):
        if r2.done.is_set():
            break
        eng.step()
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
