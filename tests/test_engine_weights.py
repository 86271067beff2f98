"""The decode engine holds its weights in the compute dtype (ISSUE 30).

``llama_decode.compute_weights`` rounds once, when the engine takes the
tree, every leaf the decode programs read through ``_cast``; the programs
then convert nothing. Held here, per engine kind: which leaves change and
which stay, that the caller's float32 arrays survive, that tokens and
logits are those of the same programs run on the float32 tree (the
per-step cast), that the lowered programs hold no weight convert, and what
``device_stats()`` reports.
"""

import ast
import dataclasses
import functools
import inspect
import re

import numpy as np
import pytest

NORM_LEAVES = ("attn_norm", "mlp_norm")

# name: engine arguments.
KINDS = {
    "paged_chunked": dict(page_tokens=16, pool_pages=40,
                          prefill_chunk_tokens=16, prefix_pool_entries=0),
    "paged_prefix": dict(page_tokens=16, pool_pages=40),
    "mesh_1x2": dict(page_tokens=16, pool_pages=40, mesh_shape=(1, 2),
                     prefix_pool_entries=0),
}


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import llama

    cfg = llama.PRESETS["debug"]        # bfloat16 compute, float32 masters
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _build(kind, model, tree="held"):
    """An engine of ``kind``. ``tree="masters"`` puts the caller's float32
    tree back under the same programs: the parent's per-step cast."""
    from ray_tpu.models import llama_decode as ld
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, capacity=128,
                       prefill_bucket=16, **KINDS[kind])
    if tree == "masters":
        eng.params = params
        if eng.mesh is not None:
            eng.params = ld.shard_decode_state(params, cfg, eng.mesh)[0]
    return eng


def _cast_leaves(params):
    from ray_tpu.models import llama_decode as ld

    for k in ld.CAST_LEAVES:
        yield k, params[k]
    for k in ld.CAST_LAYER_LEAVES:
        if k in params["layers"]:
            yield k, params["layers"][k]


PROGRAMS = ("_decode", "_paged_prefill", "_paged_suffix")


def _serve(eng):
    """Drive a fixed set of greedy requests; returns their streams and the
    first output (the sampled tokens) of every program call, in order."""
    calls = []
    programs = {n: getattr(eng, n) for n in PROGRAMS}
    for name, prog in programs.items():

        def recorded(*a, _prog=prog, _name=name, **kw):
            out = _prog(*a, **kw)
            calls.append((_name, np.asarray(out[0]).astype(np.float32)))
            return out

        setattr(eng, name, recorded)
    try:
        streams = _drive_waves(eng)
    finally:
        for name, prog in programs.items():
            setattr(eng, name, prog)
    return streams, calls


def _drive_waves(eng):
    rng = np.random.default_rng(5)
    vocab = eng.config.vocab_size
    shared = rng.integers(0, vocab, 40).tolist()
    prompts = [rng.integers(0, vocab, n).tolist() for n in (10, 37)]
    # Two waves: the second meets the first's prompt in the prefix index
    # (where the engine keeps one) and returns behind its pages.
    waves = [prompts + [shared + [3, 4, 5]], [shared + [9, 8, 7, 6]]]
    streams = []
    for wave in waves:
        reqs = [eng.submit(p, 12) for p in wave]
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
        assert all(r.status == "completed" for r in reqs)
        streams += [list(map(int, r.output)) for r in reqs]
    return streams


@pytest.fixture(scope="module")
def served(model):
    """kind -> (engine, streams, calls), each engine built and driven once."""
    cache = {}

    def get(kind):
        if kind not in cache:
            eng = _build(kind, model)
            cache[kind] = (eng,) + _serve(eng)
        return cache[kind]

    yield get
    for eng, _, _ in cache.values():
        eng.shutdown()


# ------------------------------------------------- (a) which leaves, (e)


@pytest.mark.parametrize("kind", list(KINDS))
def test_engine_holds_cast_leaves_in_compute_dtype(kind, model, served):
    import jax
    import jax.numpy as jnp

    cfg, params = model
    eng = served(kind)[0]
    held, came = eng.params, params
    assert jax.tree.structure(held) == jax.tree.structure(came)
    cast = dict(_cast_leaves(held))
    assert set(cast) == {"tok_embed", "lm_head", "wq", "wk", "wv", "wo",
                         "w_gate", "w_up", "w_down"}
    for name, w in cast.items():
        assert w.dtype == jnp.bfloat16, name
    for name in NORM_LEAVES:
        assert held["layers"][name].dtype == jnp.float32, name
    assert held["final_norm"].dtype == jnp.float32
    # Rounded once, to the value the per-step cast gave.
    assert np.array_equal(
        np.asarray(held["lm_head"].astype(jnp.float32)),
        np.asarray(came["lm_head"].astype(jnp.bfloat16)
                   .astype(jnp.float32)))
    if eng.mesh is not None:
        # Same rules, half the bytes a chip: the head is split by column.
        shard = eng.params["lm_head"].addressable_shards[0].data
        assert shard.dtype == jnp.bfloat16
        assert shard.shape == (cfg.dim, cfg.vocab_size // 2)
    stats = eng.device_stats()
    assert stats["weights_dtype"] == "bfloat16"
    want = sum(w.size * w.dtype.itemsize for w in jax.tree.leaves(eng.params))
    assert stats["weights_bytes"] == want
    masters = sum(w.size * 4 for w in jax.tree.leaves(params))
    norms = 4 * (cfg.dim * (2 * cfg.n_layers + 1))
    assert want == (masters - norms) // 2 + norms


def test_leaf_already_in_compute_dtype_is_the_same_array(model):
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    mixed = dict(params, layers=dict(params["layers"]))
    mixed["layers"]["wo"] = params["layers"]["wo"].astype(jnp.bfloat16)
    mixed["lm_head"] = params["lm_head"].astype(jnp.bfloat16)
    eng = DecodeEngine(mixed, cfg, slots=2, capacity=64)
    assert eng.params["layers"]["wo"] is mixed["layers"]["wo"]
    assert eng.params["lm_head"] is mixed["lm_head"]
    assert eng.params["layers"]["attn_norm"] is params["layers"]["attn_norm"]
    assert eng.params["layers"]["wq"].dtype == jnp.bfloat16
    # A tree the engine (or a deployment) has converted goes through again
    # without a copy.
    again = ld.compute_weights(eng.params, cfg)
    for (_, a), (_, b) in zip(_cast_leaves(again), _cast_leaves(eng.params)):
        assert a is b
    eng.shutdown()


def test_float32_compute_changes_nothing(model):
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    eng = DecodeEngine(params, cfg32, slots=2, capacity=64, page_tokens=16,
                       pool_pages=8)
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        assert a is b
    stats = eng.device_stats()
    assert stats["weights_dtype"] == "float32"
    assert stats["weights_bytes"] == sum(
        w.size * 4 for w in jax.tree.leaves(params))
    eng.shutdown()


def test_rule_names_exactly_the_leaves_the_programs_cast():
    """``_cast(<tree>["<leaf>"], ...)`` call sites of ``llama_decode`` and
    the rule's two lists are the same set of names, so neither moves
    without the other."""
    from ray_tpu.models import llama_decode as ld

    top, layer = set(), set()
    for node in ast.walk(ast.parse(inspect.getsource(ld))):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "_cast"):
            continue
        arg = node.args[0]
        assert isinstance(arg, ast.Subscript), ast.dump(arg)
        tree, leaf = arg.value.id, arg.slice.value
        assert tree in ("params", "layer"), tree
        (top if tree == "params" else layer).add(leaf)
    assert top == set(ld.CAST_LEAVES)
    assert layer == set(ld.CAST_LAYER_LEAVES)


# ------------------------------------------------ (b) the caller's arrays


@pytest.mark.parametrize("kind", list(KINDS))
def test_callers_float32_arrays_outlive_the_engine(kind, model, served):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    served(kind)
    for w in jax.tree.leaves(model[1]):
        assert w.dtype == jnp.float32 and not w.is_deleted()
        assert np.isfinite(np.asarray(w)).all()
    # ...and still serve as a reference's weights.
    out = ld.generate(model[1], [[1, 2, 3]], model[0], max_new_tokens=4)
    assert np.asarray(out).shape == (1, 4)


def test_donating_owner_frees_each_master_and_no_other_leaf(model):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_decode as ld

    cfg = model[0]
    masters = llama.init_params(cfg, jax.random.key(0))
    norm = masters["layers"]["attn_norm"]
    held = ld.compute_weights(masters, cfg, donate=True)
    for name, w in _cast_leaves(masters):
        assert w.is_deleted(), name
    assert held["layers"]["attn_norm"] is norm and not norm.is_deleted()
    for (name, a), (_, b) in zip(_cast_leaves(held),
                                 _cast_leaves(
                                     ld.compute_weights(model[1], cfg))):
        assert a.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32))), name


def test_deployment_frees_its_masters_and_serves_the_same_tokens(model):
    """``LlamaDecodeDeployment`` owns the tree it initialises: nothing of it
    stays in float32 but the norm scales, and what it serves is what an
    engine built on float32 masters of the same seed serves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.decode import DecodeEngine, LlamaDecodeDeployment

    cfg, params = model                       # seed 0, as the deployment's
    dep = LlamaDecodeDeployment(preset="debug", slots=2, capacity=64,
                                seed=0, kv_page_tokens=16, kv_pool_pages=8)
    try:
        assert not hasattr(dep, "params")
        for name, w in _cast_leaves(dep.engine.params):
            assert w.dtype == jnp.bfloat16, name
        f32 = [w for w in jax.tree.leaves(dep.engine.params)
               if w.dtype == jnp.float32]
        assert len(f32) == 3 and all(w.ndim <= 2 for w in f32)
        got = dep({"tokens": [5, 6, 7, 8], "max_new_tokens": 6})["tokens"]
    finally:
        dep.engine.shutdown()
    eng = DecodeEngine(params, cfg, slots=2, capacity=64, page_tokens=16,
                       pool_pages=8)
    req = eng.submit([5, 6, 7, 8], 6)
    while not req.done.is_set():
        eng.step()
    eng.shutdown()
    assert list(map(int, got)) == list(map(int, req.output))


# ------------------------------------- (c) same tokens, same logits


@pytest.mark.parametrize("kind", list(KINDS))
def test_streams_and_logits_are_those_of_the_float32_tree(kind, model,
                                                          served):
    """The programs on the held tree against the same programs on the
    float32 tree, which is what the parent ran every step. Bit for bit on
    this backend: both read the same bfloat16 values, rounded once here
    and once a call there (f32 -> bf16 is round-to-nearest-even in the
    eager convert and in the compiled one alike)."""
    _, streams, calls = served(kind)
    ref = _build(kind, model, tree="masters")
    try:
        want_streams, want_calls = _serve(ref)
    finally:
        ref.shutdown()
    assert streams == want_streams
    assert [n for n, _ in calls] == [n for n, _ in want_calls]
    for i, ((name, got), (_, want)) in enumerate(zip(calls, want_calls)):
        assert np.array_equal(got, want), (i, name)
    names = {n for n, _ in calls}
    need = {"paged_chunked": {"_paged_prefill", "_paged_suffix", "_decode"},
            "paged_prefix": {"_paged_prefill", "_paged_suffix", "_decode"},
            "mesh_1x2": {"_paged_prefill", "_decode"}}[kind]
    assert need <= names, names


def test_prefix_kind_returns_behind_its_prefix(served):
    eng = served("paged_prefix")[0]
    assert eng.stats()["prefix"]["hits"] >= 1


# --------------------------------- (d) no weight convert in the programs


def _weight_converts(text, shapes):
    """f32 -> bf16 ``stablehlo.convert`` lines whose operand has a weight's
    shape, whole-stack or one layer's."""
    out = []
    for m in re.finditer(
            r"stablehlo\.convert[^\n]*tensor<([0-9x]+)xf32>\) -> "
            r"tensor<[0-9x]+xbf16>", text):
        if tuple(map(int, m.group(1).split("x"))) in shapes:
            out.append(m.group(0))
    return out


@pytest.mark.parametrize("kind", ["paged_chunked", "mesh_1x2"])
def test_lowered_programs_convert_no_weight(kind, model, served):
    import jax
    import jax.numpy as jnp

    cfg, params = model
    eng = served(kind)[0]
    shapes = set()
    for _, w in _cast_leaves(params):
        shapes.add(tuple(w.shape))
        if w.shape[0] == cfg.n_layers:
            shapes.add(tuple(w.shape[1:]))
    bt = jnp.zeros((4, eng.slot_pages_max), jnp.int32)
    one = jnp.ones((1,), jnp.int32)

    def lower(tree):
        # Through a jit of the engine's callable: a mesh engine's program
        # is traced inside its axis rules, and only that wrapper knows them.
        return {
            "decode": jax.jit(eng._decode).lower(
                tree, eng.cache, jnp.asarray(eng._host_state()), bt,
                jnp.zeros((4,), jnp.float32)),
            "paged_suffix": jax.jit(functools.partial(
                eng._paged_suffix, n=1, bucket=16, width=2)).lower(
                    tree, eng.cache, jnp.zeros((1, 16), jnp.int32), one,
                    one, bt[:1, :2], one, jnp.zeros((1,), jnp.float32),
                    jnp.asarray(0, jnp.int32)),
        }

    for key, low in lower(eng.params).items():
        text = low.as_text(debug_info=True)
        assert not _weight_converts(text, shapes), key
        assert "weight_cast" not in text, key
        # ...and none on a whole stack once XLA has hoisted them out of
        # the layer loop, which is where the chip's trace showed them.
        hlo = low.compile().as_text()
        assert not re.search(
            rf"bf16\[{cfg.n_layers},[0-9,]*\][^\n]* convert\(f32\[", hlo), key
    # The control: the float32 tree under the same programs has them all.
    for key, low in lower(params).items():
        text = low.as_text(debug_info=True)
        assert len(_weight_converts(text, shapes)) >= 7, key
        assert "weight_cast" in text, key
