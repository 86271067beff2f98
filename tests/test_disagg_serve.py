"""Disaggregated prefill/decode serving (ROADMAP #3): KV-page handoff
over the object plane.

Correctness contract: the handoff itself is pure data movement, so
greedy decode through the disaggregated path is the colocated path's
token for token where both prefill a prompt whole (asserted on token
lists, a mesh-sharded decode pool included); where a prompt is prefilled
in chunks or behind a spliced prefix, the stream is held to the plain
reference within the stated margin (``tests/stream_reference.py``). And
the handoff lease (published page refs) is discharged on every path:
adopt-ack, abort, cancel/deadline, TTL expiry, and prefill-replica
SIGKILL (refs die with their owner).

Engine-level tests drive two in-process engines with explicit step();
cluster tests share one module-scoped virtual-slice cluster hosting a
prefill fleet, a decode fleet, and a decode fleet whose pages are of
another size (the adopt-mismatch fallback case).
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from stream_reference import assert_stream_is_the_references


def _tiny(max_seq_len=256):
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64,
                            max_seq_len=max_seq_len)
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _solo(params, cfg, prompt, n):
    from ray_tpu.models import llama_decode

    return list(np.asarray(llama_decode.generate(
        params, np.array([prompt], np.int32), cfg, max_new_tokens=n))[0])


def _drive(eng, reqs, steps=120):
    for _ in range(steps):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError(f"requests not done after {steps} steps")


def _adopt_payload(req):
    """The engine-level handoff payload shaped as submit(adopt=...)
    expects — what _fetch_adopt produces after the object-plane hop."""
    payload = req.handoff
    assert payload is not None, "prefill_only request captured no handoff"
    return {k: payload[k] for k in ("k", "v", "committed_len",
                                    "first_token", "page_tokens")}


def _paged(params, cfg, **kw):
    from ray_tpu.serve.decode import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("capacity", 128)
    kw.setdefault("page_tokens", 16)
    kw.setdefault("prefix_pool_entries", 0)
    return DecodeEngine(params, cfg, **kw)


# ------------------------------------------------- engine-level exact


def test_handoff_bit_exact_vs_colocated():
    """Prefill on engine A, adopt + decode on engine B: the client-
    visible token stream (first token included) is exactly the
    colocated stream, for mixed prompt lengths spanning page
    boundaries."""
    cfg, params = _tiny()
    pre = _paged(params, cfg, step_timeline=64)
    dec = _paged(params, cfg)
    for prompt in ([5, 9, 2], list(range(1, 19)), list(range(7, 47))):
        want = _solo(params, cfg, prompt, 8)
        r1 = pre.submit(prompt, max_new_tokens=8, prefill_only=True)
        _drive(pre, [r1])
        assert r1.output == []  # first token rides the descriptor
        payload = _adopt_payload(r1)
        assert payload["committed_len"] == len(prompt)
        assert payload["first_token"] == want[0]
        r2 = dec.submit(prompt, max_new_tokens=8, adopt=payload)
        _drive(dec, [r2])
        assert r2.output == want, (r2.output, want)
    assert pre.stats()["handoffs_published"] == 3
    assert dec.stats()["handoffs_adopted"] == 3
    # Steplog records the handoff capture as its own phase rows.
    rows = pre.steplog.dump()["rows"]
    assert any(ph.get("phase") == "handoff"
               for r in rows for ph in r.get("phases", []))
    pre.shutdown()
    dec.shutdown()


def test_handoff_chunked_prefill_and_prefix_hits_bit_exact():
    """The two cache-reuse paths compose with the handoff: a prefill-
    side prefix hit publishes pages it partly matched from its pool,
    and a decode-side prompt sharing the adopted prefix splices against
    the adopted pages — all streams the reference's within the stated
    margin (chunks and suffixes round unlike a whole-prompt prefill),
    and the two handoffs of one prompt give one stream."""
    cfg, params = _tiny()
    pre = _paged(params, cfg, prefill_chunk_tokens=16,
                 prefix_pool_entries=4, prefix_match_min_tokens=4)
    dec = _paged(params, cfg, prefix_pool_entries=4,
                 prefix_match_min_tokens=4)
    prompt = list(range(1, 41))  # 40 tokens: chunked prefill, 3 pages

    r1 = pre.submit(prompt, max_new_tokens=6, prefill_only=True)
    _drive(pre, [r1])
    assert pre.prefill_chunks >= 2  # actually chunked
    r2 = dec.submit(prompt, max_new_tokens=6, adopt=_adopt_payload(r1))
    _drive(dec, [r2])
    assert len(r2.output) == 6
    assert_stream_is_the_references(params, cfg, prompt, r2.output)

    # Prefill-side prefix HIT: same prompt again, matched from the pool.
    r3 = pre.submit(prompt, max_new_tokens=6, prefill_only=True)
    _drive(pre, [r3])
    assert pre.prefix.stats()["hits"] >= 1
    r4 = dec.submit(prompt, max_new_tokens=6, adopt=_adopt_payload(r3))
    _drive(dec, [r4])
    assert len(r4.output) == 6
    assert_stream_is_the_references(params, cfg, prompt, r4.output)

    # Decode-side prefix hit AGAINST THE ADOPTED PAGES: a colocated
    # request on the decode engine sharing the prompt's prefix.
    longer = prompt + [44, 45]
    r5 = dec.submit(longer, max_new_tokens=6)
    _drive(dec, [r5])
    assert dec.prefix.stats()["hits"] >= 1
    assert len(r5.output) == 6
    assert_stream_is_the_references(params, cfg, longer, r5.output)
    pre.shutdown()
    dec.shutdown()


def test_handoff_into_mesh_sharded_decode_bit_exact():
    """A single-chip prefill engine hands off to a (2, 4) GSPMD decode
    pool: the adopt scatter lands in the sharded cache and the stream
    stays the single-chip one."""
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=8,
                            n_kv_heads=8, mlp_dim=64, max_seq_len=256)
    params = llama.init_params(cfg, jax.random.key(0))
    pre = _paged(params, cfg)
    dec = _paged(params, cfg, mesh_shape=(2, 4))
    prompt = list(range(1, 23))
    want = _solo(params, cfg, prompt, 7)
    r1 = pre.submit(prompt, max_new_tokens=7, prefill_only=True)
    _drive(pre, [r1])
    r2 = dec.submit(prompt, max_new_tokens=7, adopt=_adopt_payload(r1))
    _drive(dec, [r2])
    assert r2.output == want, (r2.output, want)
    pre.shutdown()
    dec.shutdown()


def test_adopt_validation_rejects_unsplicable_handoffs():
    """Geometry the pool cannot splice is rejected at submit with the
    typed error the router maps to its colocated fallback — never a
    silent wrong-KV decode."""
    from ray_tpu.core.errors import HandoffAdoptError

    cfg, params = _tiny()
    pre = _paged(params, cfg)
    prompt = list(range(1, 19))
    r1 = pre.submit(prompt, max_new_tokens=4, prefill_only=True)
    _drive(pre, [r1])
    good = _adopt_payload(r1)

    mismatched = _paged(params, cfg, page_tokens=32)
    with pytest.raises(HandoffAdoptError, match="page_tokens"):
        mismatched.submit(prompt, max_new_tokens=4, adopt=good)
    with pytest.raises(HandoffAdoptError, match="committed_len"):
        _paged(params, cfg).submit(prompt + [3], max_new_tokens=4,
                                   adopt=good)
    # No engine is left without pages to adopt into or hand off from.
    with pytest.raises(ValueError, match="kv_page_tokens"):
        _paged(params, cfg, page_tokens=0)
    for eng in (pre, mismatched):
        eng.shutdown()


def test_cancel_deadline_mid_handoff_free_pages_both_sides():
    """Cancel/disconnect soak: prefill-only and adopted requests
    cancelled (or deadline-expired) at every lifecycle point leave
    BOTH pools drained — pages_in_use == 0, alloc fully returned,
    every slot free."""
    from ray_tpu.core.errors import (DeadlineExceededError,
                                     RequestCancelledError)

    cfg, params = _tiny()
    pre = _paged(params, cfg)
    dec = _paged(params, cfg)
    prompt = list(range(1, 35))
    for _ in range(3):
        # (a) prefill-only cancelled while queued: never seats.
        ra = pre.submit(prompt, max_new_tokens=4, prefill_only=True)
        assert pre.cancel(ra.request_id)
        # (b) a handoff that completes, then the adopted request is
        # cancelled mid-decode on the far side.
        rb = pre.submit(prompt, max_new_tokens=20, prefill_only=True)
        _drive(pre, [ra, rb])
        with pytest.raises(RequestCancelledError):
            ra.raise_for_status()
        rc = dec.submit(prompt, max_new_tokens=20,
                        adopt=_adopt_payload(rb))
        dec.step()
        assert dec.cancel(rc.request_id)
        # (c) adopted request whose deadline expires mid-decode.
        rd = dec.submit(prompt, max_new_tokens=50,
                        adopt=_adopt_payload(rb), deadline_s=0.05)
        time.sleep(0.06)
        _drive(dec, [rc, rd])
        with pytest.raises(RequestCancelledError):
            rc.raise_for_status()
        with pytest.raises(DeadlineExceededError):
            rd.raise_for_status()
    for eng in (pre, dec):
        s = eng.stats()
        assert s["pages_in_use"] == 0, s
        assert s["pages_free"] == s["pages_total"], s
        assert s["free_slots"] == s["slots"], s
        eng.shutdown()


# ------------------------------------------------- ledger + autoscaler


def test_handoff_ledger_lease_discipline():
    """Publish/discharge/sweep accounting: discharge is idempotent,
    sweep expires only past-TTL entries, live()/live_bytes() track the
    open window."""
    from ray_tpu.serve.handoff import (HANDOFF_DESC_BYTE_BUDGET,
                                       HandoffLedger, descriptor_nbytes)

    led = HandoffLedger(ttl_s=30.0)
    desc = {"handoff_id": "h1", "nbytes": 4096, "page_tokens": 16}
    led.publish_handoff(desc)
    assert led.live() == 1 and led.live_bytes() == 4096
    assert descriptor_nbytes(desc) < HANDOFF_DESC_BYTE_BUDGET
    entry = led.discharge_handoff("h1")
    assert entry["desc"] is desc and entry["age_s"] >= 0
    assert led.discharge_handoff("h1") is None  # idempotent
    assert led.live() == 0

    led.publish_handoff({"handoff_id": "h2", "nbytes": 1})
    assert led.sweep() == []  # fresh: not expired
    expired = led.sweep(now=time.monotonic() + 31.0)
    assert [e["desc"]["handoff_id"] for e in expired] == ["h2"]
    assert led.live() == 0


def test_autoscale_load_is_the_worse_of_ongoing_and_backlog():
    """The autoscaler's per-replica load: HTTP concurrency or the
    engine's own backlog, whichever is worse."""
    from ray_tpu.serve.controller import autoscale_load

    assert autoscale_load({"ongoing": 2, "load": 5}) == 5.0
    assert autoscale_load({"ongoing": 3}) == 3.0
    assert autoscale_load({}) == 0.0


def test_deployment_role_validation_and_config():
    """Role plumbing: invalid roles and prefill-without-decode rejected
    at declaration; role/decode_deployment survive options() copies and
    land in config_dict (the controller snapshot's source)."""
    from ray_tpu.serve.deployment import Deployment

    class D:
        pass

    with pytest.raises(ValueError, match="role"):
        Deployment(D, role="prefit")
    with pytest.raises(ValueError, match="decode_deployment"):
        Deployment(D, role="prefill")
    dep = Deployment(D, role="prefill", decode_deployment="dec")
    dep2 = dep.options(num_replicas=2)
    assert dep2.role == "prefill"
    assert dep2.decode_deployment == "dec"
    cfg = dep2.config_dict()
    assert cfg["role"] == "prefill"
    assert cfg["decode_deployment"] == "dec"
    # Legacy declaration: role stays unset (None), the colocated path.
    assert Deployment(D).config_dict()["role"] is None


# ------------------------------------------------- cluster end-to-end


def _make_prefill_cls():
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    class PrefillDecode(LlamaDecodeDeployment):
        def pid(self, _=None):
            return os.getpid()

    return PrefillDecode


@pytest.fixture(scope="module")
def disagg_cluster():
    """One virtual-slice cluster hosting the whole disagg topology:
    a decode fleet, a prefill fleet spliced onto it, and a decode fleet
    whose pages are the default 64 tokens, not the prefill fleet's 16
    (the adopt-mismatch fallback target)."""
    from ray_tpu.models import llama

    core = ray_tpu.init(num_cpus=8)
    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=256)
    serve.run(
        serve.deployment(_make_prefill_cls(), role="decode").options(
            max_concurrency=4).bind(
            config=cfg, slots=2, capacity=128, kv_page_tokens=16,
            prefix_pool_entries=4, prefix_match_min_tokens=4),
        name="dg-decode")
    serve.run(
        serve.deployment(_make_prefill_cls(), role="prefill",
                         decode_deployment="dg-decode").options(
            max_concurrency=4).bind(
            config=cfg, slots=2, capacity=128, kv_page_tokens=16,
            prefill_chunk_tokens=16,
            prefix_pool_entries=4, prefix_match_min_tokens=4),
        name="dg-prefill")
    serve.run(
        serve.deployment(_make_prefill_cls(), role="decode").options(
            max_concurrency=4).bind(config=cfg, slots=2, capacity=128),
        name="dg-plain")
    yield core, cfg
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def _handoffs_drained(name, timeout=30.0):
    deadline = time.monotonic() + timeout
    live = None
    while time.monotonic() < deadline:
        live = serve.status().get(name, {}).get("handoffs_live")
        if live == 0:
            return True
        time.sleep(0.25)
    raise AssertionError(f"{name} handoffs never drained: {live}")


@pytest.mark.slow  # PR 17 rebudget (9.1s): cluster-level bit-exactness;
#   engine-level exactness, the splice path (slo/fallback tests) and the
#   SIGKILL heal stay tier-1
@pytest.mark.timeout_s(300)
def test_disagg_serving_unary_and_stream_bit_exact(disagg_cluster):
    """The full splice through the router: requests to the prefill
    fleet come back exactly the colocated stream (greedy ground truth
    from llama_decode.generate), unary and streaming, and every
    published lease is discharged."""
    import jax

    from ray_tpu.models import llama

    _core, cfg = disagg_cluster
    params = llama.init_params(cfg, jax.random.key(0))
    handle = serve.get_deployment_handle("dg-prefill")

    prompt = list(range(1, 29))
    want = _solo(params, cfg, prompt, 6)
    out = handle.remote({"tokens": prompt,
                         "max_new_tokens": 6}).result(timeout=180)
    assert out["tokens"] == want, (out["tokens"], want)

    toks = list(handle.stream({"tokens": prompt, "max_new_tokens": 6,
                               "stream": True}))
    assert toks == want

    # A prefix-sharing second request stays exact through the splice.
    longer = prompt + [31, 32]
    out2 = handle.remote({"tokens": longer,
                          "max_new_tokens": 6}).result(timeout=180)
    assert out2["tokens"] == _solo(params, cfg, longer, 6)

    # Topology + lease accounting through serve.status().
    status = serve.status()
    assert status["dg-prefill"]["role"] == "prefill"
    assert status["dg-prefill"]["decode_deployment"] == "dg-decode"
    assert status["dg-decode"]["role"] == "decode"
    _handoffs_drained("dg-prefill")


@pytest.mark.slow  # PR 20 rebudget (10.3s): SLO-panel plumbing;
# disagg handoff correctness gates stay tier-1
@pytest.mark.timeout_s(300)
def test_disagg_slo_metrics_reach_status(disagg_cluster):
    """Handoff SLO instruments flow engine -> flusher -> controller ->
    slo_summary: descriptor bytes under budget, publish->adopt latency
    observed, and the event counter books balance (published ==
    adopted + aborted + expired once drained). Drives its own spliced
    traffic (must not depend on the slow-marked e2e test having run)."""
    from ray_tpu.serve.handoff import HANDOFF_DESC_BYTE_BUDGET

    handle = serve.get_deployment_handle("dg-prefill")
    handle.remote({"tokens": list(range(1, 25)),
                   "max_new_tokens": 4}).result(timeout=180)

    deadline = time.monotonic() + 120
    slo = {}
    while time.monotonic() < deadline:
        slo = serve.status().get("dg-prefill", {}).get("slo", {})
        # Latency observes at adopt-ack; wait for the ack to flush, not
        # just the publish.
        if slo.get("handoffs", {}).get("adopted"):
            break
        time.sleep(0.5)
    hand = slo.get("handoffs", {})
    assert hand.get("published") and hand.get("adopted"), slo
    bytes_h = slo.get("handoff_bytes", {})
    assert bytes_h.get("count", 0) >= 1
    assert bytes_h.get("p99", 1e9) <= HANDOFF_DESC_BYTE_BUDGET
    assert slo.get("handoff_latency_s", {}).get("count", 0) >= 1
    _handoffs_drained("dg-prefill")
    hand = serve.status()["dg-prefill"]["slo"]["handoffs"]
    assert hand["published"] == (hand.get("adopted", 0)
                                 + hand.get("aborted", 0)
                                 + hand.get("expired", 0)), hand


@pytest.mark.timeout_s(300)
def test_disagg_fallback_when_decode_cannot_adopt(disagg_cluster):
    """Splice onto a decode fleet whose pool cannot adopt (pages of
    another size): the typed adopt error walks back through the router,
    the lease is aborted, and the request completes COLOCATED on the
    prefill replica (chunked there, so held to the reference within the
    stated margin), zero live leases."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.deployment import _Router

    _core, cfg = disagg_cluster
    params = llama.init_params(cfg, jax.random.key(0))
    handle = serve.get_deployment_handle("dg-prefill")
    router = _Router.get("dg-prefill")
    prompt = list(range(3, 27))
    orig = router._decode_dep
    router._decode_dep = "dg-plain"
    try:
        out = handle.remote({"tokens": prompt,
                             "max_new_tokens": 5}).result(timeout=180)
    finally:
        router._decode_dep = orig
    assert len(out["tokens"]) == 5
    assert_stream_is_the_references(params, cfg, prompt, out["tokens"])
    _handoffs_drained("dg-prefill")

    # No decode fleet routable at all (snapshotless name): the splice
    # is skipped up front and the request runs the legacy path.
    router._decode_dep = "dg-ghost"
    try:
        out = handle.remote({"tokens": prompt,
                             "max_new_tokens": 5}).result(timeout=180)
    finally:
        router._decode_dep = orig
    assert len(out["tokens"]) == 5
    assert_stream_is_the_references(params, cfg, prompt, out["tokens"])


@pytest.mark.chaos
@pytest.mark.timeout_s(300)
def test_prefill_sigkill_mid_handoff_no_leaked_refs(disagg_cluster):
    """SIGKILL the prefill replica while it holds a published,
    undischarged handoff: the payload refs died with their owner (no
    leak, nothing to sweep), and the next request re-prefills on the
    controller's replacement replica with an exact stream."""
    import jax

    from ray_tpu.models import llama

    _core, cfg = disagg_cluster
    params = llama.init_params(cfg, jax.random.key(0))
    handle = serve.get_deployment_handle("dg-prefill")
    prompt = list(range(2, 26))

    # Publish a lease directly (no decode side picks it up).
    desc = handle.options(method_name="prefill_handoff").remote(
        {"tokens": prompt, "max_new_tokens": 4}).result(timeout=180)
    victim = handle.options(method_name="pid").remote(None).result(
        timeout=60)
    os.kill(victim, signal.SIGKILL)

    # The refs' owner is gone: fetching the payload fails (structural
    # free — zero leaked refs, no TTL sweep needed).
    with pytest.raises(Exception):
        ray_tpu.get(desc["k_ref"], timeout=10)

    # The controller replaces the replica; the full splice works again
    # and re-prefills from scratch, exactly.
    want = _solo(params, cfg, prompt, 4)
    deadline = time.monotonic() + 150
    out = None
    while time.monotonic() < deadline:
        try:
            out = handle.remote({"tokens": prompt,
                                 "max_new_tokens": 4}).result(timeout=60)
            break
        except Exception:
            time.sleep(1.0)
    assert out is not None, "prefill fleet never healed after SIGKILL"
    assert out["tokens"] == want
    _handoffs_drained("dg-prefill")


def test_handoff_payload_owns_its_bytes():
    """Regression (the PR 16 pin, now lint-pinned by graftlint
    donation-asarray-alias): the captured K/V handoff payload must OWN
    its bytes. np.asarray would hand back a host VIEW of the paged
    cache, and the engine's next donated dispatch would clobber a
    payload already published to the object plane."""
    cfg, params = _tiny()
    pre = _paged(params, cfg)
    r1 = pre.submit(list(range(1, 19)), max_new_tokens=4,
                    prefill_only=True)
    _drive(pre, [r1])
    for key in ("k", "v"):
        arr = r1.handoff[key]
        assert isinstance(arr, np.ndarray)
        assert arr.flags["OWNDATA"] and arr.base is None, key
    # The payload survives further donated engine work verbatim.
    k0 = r1.handoff["k"].copy()
    r2 = pre.submit([7, 3, 11], max_new_tokens=4)
    _drive(pre, [r2])
    assert np.array_equal(k0, r1.handoff["k"])
    pre.shutdown()
