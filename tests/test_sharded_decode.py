"""GSPMD model-parallel decode: the sharded programs against the
single-chip ones, engine integration, and mesh-native serving end-to-end.

The correctness contract: a mesh layout changes logits by no more than
two programs of the same math may differ in bfloat16
(``tests/stream_reference.py::LOGITS_ATOL``). The decode rules partition
only output/batch dims and all-gather before every contracted operand
(``wo``/``w_down`` replicated), so no reduction is split across chips;
what differs is how XLA fuses and orders each chip's share. Asserted
across mesh shapes 1x8 / 2x4 / 8x1 on the virtual CPU mesh for the paged
prefill, suffix prefill and decode programs under the pool's sharding.
"""

import json
import time
from functools import partial

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve

MESHES = [(1, 8), (2, 4), (8, 1)]


def _cfg():
    from ray_tpu.models import llama

    # every sharded dim divisible by 8 so all three mesh shapes exercise
    # real weight sharding (indivisible configs replicate — tested
    # separately)
    return llama.LlamaConfig(vocab_size=64, dim=32, n_layers=2,
                             n_heads=8, n_kv_heads=8, mlp_dim=64,
                             max_seq_len=128)


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import llama

    cfg = _cfg()
    return cfg, llama.init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(0)
    return rng.randint(1, 60, size=(8, 12)).astype(np.int32)


# ------------------------------------------- model-level, paged programs

T, PAGES, W, HALF = 8, 80, 8, 6


def _paged_logits(model, prompts, shardings=None):
    """Logits of the three paged programs the engine serves with: a
    whole-prompt prefill followed by four decode steps, and a suffix
    prefill behind the first ``HALF`` tokens' pages. On a mesh,
    ``shardings`` is ``(replicated, pool)``: where the programs' host-
    facing outputs and the pool they pass along live."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    cfg, params = model
    B, S = prompts.shape

    def jit_kw(n):  # a program with n replicated outputs beside the pool
        if shardings is None:
            return {}
        rep, pool_sh = shardings
        return {"out_shardings": (rep, pool_sh) + (rep,) * (n - 1)}

    def place(pool):
        return (pool if shardings is None
                else jax.device_put(pool, shardings[1]))

    bt = jnp.asarray(
        np.arange(1, 1 + B * W, dtype=np.int32).reshape(B, W))
    out = {}
    ppf = jax.jit(partial(ld.paged_prefill, config=cfg), **jit_kw(1))
    lg, pool = ppf(params, jnp.asarray(prompts),
                   place(ld.init_page_pool(cfg, PAGES, T)), bt)
    out["prefill"] = np.asarray(lg)
    pd = jax.jit(partial(ld.paged_decode_step, config=cfg), **jit_kw(2))
    lens = jnp.full((B,), S, jnp.int32)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    # The step's view: every page of every row, padded to a wider rung.
    view = jnp.asarray(ld.live_page_view(
        np.asarray(bt), np.full((B,), W), 2 * B * W))
    for _ in range(4):
        lg, pool, lens = pd(params, pool, view, lens, tok)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    out["decode"] = np.asarray(lg)

    _, warm = ppf(params, jnp.asarray(prompts[:, :HALF]),
                  place(ld.init_page_pool(cfg, PAGES, T)), bt)
    sfx = jax.jit(partial(ld.paged_prefill_suffix, config=cfg),
                  **jit_kw(1))
    slg, _ = sfx(params, jnp.asarray(prompts[:, HALF:]), warm, bt,
                 prefix_lens=jnp.full((B,), HALF, jnp.int32),
                 lengths=jnp.full((B,), S, jnp.int32))
    out["suffix"] = np.asarray(slg)
    return out


@pytest.fixture(scope="module")
def references(model, prompts):
    """The single-chip programs' logits."""
    return _paged_logits(model, prompts)


@pytest.mark.parametrize("shape", MESHES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_sharded_logits_match_single_chip(model, prompts, references,
                                          shape):
    """Paged prefill, decode and suffix-prefill logits on every mesh
    shape are the single-chip programs' within ``LOGITS_ATOL``."""
    from ray_tpu.models import llama_decode as ld
    from ray_tpu.parallel.mesh import decode_mesh
    from ray_tpu.parallel.sharding import axis_rules
    from stream_reference import assert_logits_close

    cfg, params = model
    mesh = decode_mesh(shape)
    sparams, sh = ld.shard_decode_state(params, cfg, mesh)
    pool_sh = {"k": sh["pool"]["k"], "v": sh["pool"]["v"]}
    with axis_rules(mesh, sh["rules"]):
        got = _paged_logits((cfg, sparams), prompts,
                            (sh["replicated"], pool_sh))
    for name, want in references.items():
        assert_logits_close(got[name], want)


def test_indivisible_dims_replicate_not_pad(model):
    """A GQA config whose kv heads don't divide the model axis
    replicates the head dims (mlp still shards)."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import decode_mesh
    from ray_tpu.parallel.sharding import decode_rules

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64, max_seq_len=128)
    rules = decode_rules(cfg, decode_mesh((2, 4)))
    assert rules["kv_heads"] is None and rules["heads"] is None
    assert rules["vocab"] is None      # 61 % 4 != 0
    assert rules["mlp"] == "model"     # 64 % 4 == 0
    # and (8, 1): model axis 1 -> everything effectively unsharded
    rules1 = decode_rules(cfg, decode_mesh((8, 1)))
    assert rules1["heads"] == "model"  # axis size 1: moot but legal


def test_decode_param_axes_replicates_contraction_operands():
    from ray_tpu.models import llama

    axes = llama.decode_param_axes(_cfg())
    assert axes["layers"]["wo"] == ("layers", None, None, None)
    assert axes["layers"]["w_down"] == ("layers", None, None)
    # output-dim projections still shard
    assert axes["layers"]["wq"][2] == "heads"
    assert axes["lm_head"][1] == "vocab"


# --------------------------------------------------------- engine level


def _drive(eng, prompts, n_tok=6):
    reqs = [eng.submit(list(p), max_new_tokens=n_tok)
            for p in prompts]
    for _ in range(120):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    return [r.output for r in reqs]


def test_engine_mesh_matches_single_chip(model):
    """The full continuous-batching engine (admission waves, prefix
    suffix splice, paged pool, chunked prefill) emits identical token
    streams with and without a mesh."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    prompts = [[5, 9, 2], [7], [11, 3, 4, 8, 1], [9, 1]]
    ref = _drive(DecodeEngine(params, cfg, slots=4, capacity=64),
                 prompts)
    out = _drive(DecodeEngine(params, cfg, slots=4, capacity=64,
                              mesh_shape=(2, 4)), prompts)
    assert out == ref

    paged_kw = dict(page_tokens=8, pool_pages=40, prefix_pool_entries=8,
                    prefill_chunk_tokens=16)
    ref_p = _drive(DecodeEngine(params, cfg, slots=4, capacity=64,
                                **paged_kw), prompts)
    out_p = _drive(DecodeEngine(params, cfg, slots=4, capacity=64,
                                mesh_shape=(2, 4), **paged_kw), prompts)
    assert out_p == ref_p


def test_engine_validates_slot_divisibility(model):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    with pytest.raises(ValueError, match="multiple of the mesh"):
        DecodeEngine(params, cfg, slots=3, capacity=64,
                     mesh_shape=(2, 4))


def test_engine_stats_report_mesh(model):
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, capacity=64,
                       mesh_shape=(4, 2))
    s = eng.stats()
    assert s["chips"] == 8 and s["mesh_shape"] == [4, 2]
    single = DecodeEngine(params, cfg, slots=2, capacity=64)
    assert single.stats()["chips"] == 1
    assert single.stats()["mesh_shape"] is None


# ------------------------------------------------------- router slice


def test_router_prefers_ici_local_replica(monkeypatch):
    """With two unsaturated replicas on different slices, the router
    picks the one on the caller's own slice (controller snapshots carry
    slice ids; locality never overrides saturation)."""
    import importlib

    # ray_tpu.serve re-exports the @deployment decorator under the same
    # name as the module; import the module itself.
    dep_mod = importlib.import_module("ray_tpu.serve.deployment")

    router = dep_mod._Router.__new__(dep_mod._Router)
    import threading

    router.name = "t"
    router._lock = threading.Lock()
    router._inflight = {}
    router._version = 1
    router._max_ongoing = 2
    router._deleted = False
    router._replicas = [
        {"handle": object(), "id": "a", "models": set(),
         "prefixes": set(), "slice_id": "far"},
        {"handle": object(), "id": "b", "models": set(),
         "prefixes": set(), "slice_id": "here"},
    ]
    monkeypatch.setattr(dep_mod, "_local_slice_cache", ["here"])
    for _ in range(8):
        chosen = router._pick("")
        assert chosen["id"] == "b"
        router._release(chosen)
    # saturated local replica: load escapes locality
    router._inflight["b"] = 2
    assert router._pick("")["id"] == "a"


# -------------------------------------------------- serve plane e2e


@pytest.fixture
def mesh_serve_cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_VIRTUAL_SLICE", "2x4")
    core = ray_tpu.init(num_cpus=4)
    yield core
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.mark.timeout_s(420)
def test_mesh_replica_serves_end_to_end(mesh_serve_cluster, model):
    """Acceptance: a deployment with mesh_shape=(2, 4) spawns ONE
    replica spanning all 8 virtual devices, streams through proxy ->
    router -> replica, its outputs are bit-exact vs the single-chip
    engine at equal capacity, status reports the topology, and a second
    8-chip deployment is refused placement until the slice frees."""
    import urllib.request

    from ray_tpu.serve.decode import DecodeEngine, LlamaDecodeDeployment

    cfg, params = model
    ref = _drive(DecodeEngine(params, cfg, slots=4, capacity=64),
                 [[5, 9, 2]], n_tok=5)[0]

    serve.run(
        serve.deployment(LlamaDecodeDeployment).options(
            max_concurrency=4).bind(config=cfg, slots=4, capacity=64,
                                    seed=0, mesh_shape=(2, 4)),
        name="llm", ready_timeout_s=180)
    handle = serve.get_deployment_handle("llm")
    out = handle.remote({"tokens": [5, 9, 2],
                         "max_new_tokens": 5}).result(timeout=180)
    assert out["tokens"] == ref

    toks = list(handle.stream({"tokens": [5, 9, 2], "max_new_tokens": 5,
                               "stream": True}))
    assert toks == ref

    host, port = serve.start_http()
    req = urllib.request.Request(
        f"http://{host}:{port}/llm",
        data=json.dumps({"tokens": [5, 9, 2], "max_new_tokens": 5,
                         "stream": True}).encode(),
        headers={"X-Serve-Stream": "1"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        lines = [json.loads(ln) for ln in resp.read().splitlines() if ln]
    assert lines == ref

    # one replica spans the whole slice, and status says where it lives
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["llm"]
        if st["replica_topology"] and \
                st["replica_topology"][0]["mesh_shape"]:
            break
        time.sleep(0.5)
    assert st["replicas"] == 1
    assert st["chips_in_use"] == 8
    topo = st["replica_topology"][0]
    assert topo["mesh_shape"] == [2, 4] and topo["chips"] == 8
    assert topo["slice_id"].startswith("virtual-")
    assert topo["sub_slice"] == {"origin": [0, 0], "shape": [2, 4]}

    # the slice is fully reserved: a second 8-chip replica is refused —
    # the deployment stays at 0 replicas (queued), it is never placed
    # on a fragment
    serve.run(
        serve.deployment(LlamaDecodeDeployment, name="llm2").options(
            max_concurrency=2).bind(config=cfg, slots=4, capacity=64,
                                    mesh_shape=(2, 4)),
        name="llm2", ready_timeout_s=15)
    time.sleep(1.5)
    st2 = serve.status()["llm2"]
    assert st2["replicas"] == 0 and st2["chips_in_use"] == 0
    slice_state = list(
        ray_tpu.cluster_topology()["slices"].values())[0]
    assert slice_state["chips_free"] == 0
    assert len(slice_state["reservations"]) == 1

    # freeing the slice lets the queued deployment place (reconcile)
    serve.delete("llm")
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        st2 = serve.status().get("llm2", {})
        if st2.get("replicas"):
            break
        time.sleep(0.5)
    assert st2.get("replicas") == 1
    assert st2.get("chips_in_use") == 8
