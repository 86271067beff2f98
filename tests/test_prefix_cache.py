"""Prefix KV cache: the prefix format the index and the router share
(``serve/paging.py``; the index's own units are in test_paged_kv.py), the
decode engine's splice + suffix-prefill admission path against the solo
reference, and prefix-affinity routing. All CPU, tiny configs — tier-1
safe."""

import threading

import numpy as np
import pytest

from ray_tpu.serve.paging import (PageAllocator, PagedPrefixIndex,
                                  bucket_lengths, candidate_hashes,
                                  prefix_hash)


def _tiny():
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


# ------------------------------------------------------------ host index


def test_bucket_lengths_grid():
    assert bucket_lengths(100, 16) == [64, 32, 16]
    assert bucket_lengths(64, 16) == [64, 32, 16]
    assert bucket_lengths(15, 16) == []
    assert bucket_lengths(100, 16, cap=32) == [32, 16]


def test_candidate_hashes_match_advertised_entries():
    """The router's candidate grid and the index's insert grid agree, so
    every advertised hash is discoverable from the raw prompt, and the
    longest is the router's first candidate."""
    toks = list(range(100))
    pa = PageAllocator(8)
    idx = PagedPrefixIndex(pa, page_tokens=16, max_pages=8, min_tokens=16)
    idx.insert(toks, pa.alloc(7))  # chain of 4 pages up to length 64
    cands = candidate_hashes(toks, 16)
    assert cands[0] == prefix_hash(toks[:64])
    assert set(idx.hashes()) == set(cands)  # lengths 64, 32, 16


# ------------------------------------------------ engine admission


def test_engine_prefix_hits_bit_exact():
    """Continuous batching with the prefix cache ON produces exactly the
    solo-generate stream for every request, across cold insert, full-hit
    and partial-hit admissions."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, 20).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, 6).tolist()
               for _ in range(3)]
    # Partial hit: diverges inside the cached entry.
    prompts.append(shared[:9] + rng.integers(0, cfg.vocab_size,
                                             8).tolist())
    eng = DecodeEngine(params, cfg, slots=2, capacity=64, page_tokens=4,
                       prefix_pool_entries=4, prefix_match_min_tokens=4)
    hits = 0
    for p in prompts:
        req = eng.submit(p, max_new_tokens=5)
        for _ in range(40):
            if req.done.is_set():
                break
            eng.step()
        solo = np.asarray(llama_decode.generate(
            params, np.array([p], np.int32), cfg, max_new_tokens=5))[0]
        assert req.output == list(solo), (req.output, list(solo))
        hits += req.prefix_len > 0
    assert hits == 3  # all but the cold first admission
    stats = eng.prefix.stats()
    assert stats["hits"] == 3 and stats["prefill_tokens_saved"] > 0
    # Partial-hit request matched up to the last whole page before the
    # divergence point, not beyond.
    assert req.prefix_len == 8
    eng.shutdown()


def test_engine_prefix_batched_hit_wave():
    """A whole admission wave of prefix hits (batched suffix prefill,
    padded to a power of two) stays bit-exact."""
    from ray_tpu.models import llama_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 16).tolist()
    eng = DecodeEngine(params, cfg, slots=4, capacity=64, page_tokens=4,
                       prefix_pool_entries=4, prefix_match_min_tokens=4)
    warm = eng.submit(shared + [7, 7], max_new_tokens=1)
    while not warm.done.is_set():
        eng.step()
    prompts = [shared + rng.integers(0, cfg.vocab_size, 5).tolist()
               for _ in range(3)]  # wave of 3 -> padded to n=4
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    for _ in range(40):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.prefix_len == 16 for r in reqs)
    for req, p in zip(reqs, prompts):
        solo = np.asarray(llama_decode.generate(
            params, np.array([p], np.int32), cfg, max_new_tokens=4))[0]
        assert req.output == list(solo), (req.output, list(solo))
    eng.shutdown()


def test_engine_disabled_pool_allocates_nothing():
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64,
                       prefix_pool_entries=0)
    assert eng.prefix is None
    req = eng.submit([1, 2, 3], max_new_tokens=3)
    for _ in range(10):
        if req.done.is_set():
            break
        eng.step()
    assert len(req.output) == 3
    stats = eng.stats()
    assert "prefix" not in stats and stats["pages_pinned"] == 0
    assert stats["pages_free"] == stats["pages_total"]  # nothing pinned
    eng.shutdown()


def test_engine_load_counts_backlog():
    """Replica load = occupied slots + pending queue depth: a saturated
    engine with a deep queue must not look idle to the autoscaler."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64,
                       prefix_pool_entries=0)
    reqs = [eng.submit([i + 1, 2], max_new_tokens=8) for i in range(5)]
    eng.step()  # admit 2, leave 3 queued
    s = eng.stats()
    assert s["active"] == 2 and s["queued"] == 3 and s["load"] == 5
    assert s["slots"] == 2
    for _ in range(60):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert eng.stats()["load"] == 0
    eng.shutdown()


# ------------------------------------------------------ affinity routing


def test_router_prefers_prefix_resident_replica():
    from ray_tpu.serve.deployment import _Router

    toks = list(range(64))
    h = prefix_hash(np.asarray(toks[:64], np.int32))
    router = object.__new__(_Router)
    router._lock = threading.Lock()
    router._max_ongoing = 8
    router._inflight = {}
    router._replicas = [
        {"id": "cold", "models": set(), "prefixes": set()},
        {"id": "warm", "models": set(), "prefixes": {h}},
    ]
    hashes = candidate_hashes(toks, 16)
    assert hashes[0] == h
    for _ in range(4):
        assert router._pick("", hashes)["id"] == "warm"
    # Saturated warm replica: affinity yields to least-loaded.
    router._inflight["warm"] = 8
    assert router._pick("", hashes)["id"] == "cold"


def test_affinity_hashes_extraction():
    from ray_tpu.core.config import config as rt_config
    from ray_tpu.serve.deployment import _affinity_hashes

    toks = list(range(40))
    hashes = _affinity_hashes(({"tokens": toks},))
    assert hashes == candidate_hashes(toks,
                                      rt_config.prefix_match_min_tokens)
    assert _affinity_hashes(()) is None
    assert _affinity_hashes(("not-a-dict",)) is None
    assert _affinity_hashes(({"no_tokens": 1},)) is None
    old = rt_config.prefix_affinity_enabled
    try:
        rt_config.prefix_affinity_enabled = False
        assert _affinity_hashes(({"tokens": toks},)) is None
    finally:
        rt_config.prefix_affinity_enabled = old
