"""MiMo-V2 (``models/mimo.py``, ``models/mimo_decode.py``) against its plain
reference (``benchmarks/reference/mimo_v2_ref.py``) at the debug preset, in
float32 on the CPU: whole prefill, chunked prefill and decode through the
engine's two kinds of page, LOGITS compared, over prompts that cross the
window (12), a page (4) and a chunk (32); each of the model's own pieces
changes the result when it is left out; the 16 shares of an expert layer add
up to the uncut layer."""

import dataclasses

import numpy as np
import pytest

TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import mimo

    cfg = mimo.PRESETS["debug"]
    return cfg, mimo.init_params(cfg, jax.random.key(7))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _tables(first_pages, window_pages, window_first):
    import jax.numpy as jnp

    return {"full": jnp.asarray([first_pages], jnp.int32),
            "window": jnp.asarray([window_pages], jnp.int32),
            "window_first": jnp.asarray([window_first], jnp.int32)}


def _prefilled(cfg, params, tokens, chunk, T=4):
    """``tokens`` through ``paged_prefill_suffix`` in chunks of ``chunk``
    over hand-made tables: full pages 1.., window pages written through
    and never freed (the engine's freeing is ``test_page_kinds``'s).
    Returns the last chunk's logits and the pool."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo_decode as md

    n = len(tokens)
    pages = -(-n // T) + 2
    pool = md.init_page_pool(cfg, {"full": pages, "window": pages}, T)
    ids = list(range(1, pages + 1))
    logits = None
    for p in range(0, n, chunk):
        part = tokens[p:p + chunk]
        first = max(0, p - cfg.window + 1) // T
        width = -(-(len(part) + cfg.window) // T) + 1
        logits, pool = md.paged_prefill_suffix(
            params, jnp.asarray(part)[None], pool,
            _tables(ids, (ids[first:] + [0] * width)[:width], first), cfg,
            jnp.asarray([p], jnp.int32),
            jnp.asarray([p + len(part)], jnp.int32))
    return np.asarray(logits[0]), pool, ids


def _reference(cfg, params, tokens, rows):
    from benchmarks.reference import mimo_v2_ref

    return np.asarray(mimo_v2_ref.logits(params, tokens, cfg, rows=rows))


@pytest.mark.parametrize("n,chunk", [(37, 64), (37, 16), (70, 32)])
def test_prefill_whole_and_chunked_gives_the_references_logits(model, n,
                                                               chunk):
    cfg, params = model
    tokens = _tokens(cfg, n)
    got, _, _ = _prefilled(cfg, params, tokens, chunk)
    want = _reference(cfg, params, tokens, [n - 1])[0]
    assert np.abs(got - want).max() < TOL


def test_decode_steps_over_both_kinds_give_the_references_logits(model):
    """Eight decode steps after a chunked prefill of 30 tokens, the view
    built by ``live_page_view`` from the window's last pages only."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo_decode as md

    cfg, params = model
    T, n, steps = 4, 30, 8
    tokens = _tokens(cfg, n + steps, seed=1)
    _, pool, ids = _prefilled(cfg, params, tokens[:n], 16)
    want = _reference(cfg, params, tokens, list(range(n, n + steps)))
    table = np.zeros((2, 16), np.int32)
    table[1, :len(ids)] = ids
    for j in range(steps):
        pos = n + j
        first = max(0, pos - cfg.window + 1) // T
        held = pos // T + 1 - first
        view = md.live_page_view(
            {"full": table, "window": table},
            {"full": np.asarray([0, pos // T + 1]),
             "window": (np.asarray([0, first]), np.asarray([0, held]))},
            {"full": 16, "window": 4})
        logits, pool, lens, stats = md.paged_decode_step(
            params, pool, {k: jnp.asarray(v) for k, v in view.items()},
            jnp.asarray([0, pos], jnp.int32),
            jnp.asarray([0, tokens[pos]], jnp.int32), cfg)
        assert np.abs(np.asarray(logits[1]) - want[j]).max() < TOL, j
        assert int(lens[1]) == pos + 1 and stats.shape == (3,)
        assert float(stats[0]) <= 6 * cfg.top_k     # slot 1's pairs only


@pytest.mark.parametrize("view_block", [512, 16])
def test_the_engine_serves_the_references_tokens(model, monkeypatch,
                                                 view_block):
    """Through ``DecodeEngine``: admission, chunks, window pages freed as
    they are passed, four prompts at once; the decode reads its list of
    full pages in one block, and in blocks of one group (the running
    softmax across blocks, and a loop that stops at the live rows)."""
    from benchmarks.reference import mimo_v2_ref
    from ray_tpu.models import mimo_decode
    from ray_tpu.serve.decode import DecodeEngine

    monkeypatch.setattr(mimo_decode, "VIEW_BLOCK", view_block)
    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, capacity=256, page_tokens=4,
                       prefill_chunk_tokens=32, model=mimo_decode,
                       step_timeline=0, metrics_enabled=False,
                       trace_spans=False)
    prompts = [_tokens(cfg, n, seed=n).tolist() for n in (50, 7, 100, 33)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (20, 30, 10, 5))]
    for _ in range(300):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            break
    margins = mimo_v2_ref.served_token_margins(
        eng.params, cfg, prompts, [r.output for r in reqs])
    assert len(margins) == 65 and max(margins) < TOL
    eng.shutdown()


def _without(cfg, params, what):
    """The model with one of its pieces left out."""
    import jax
    import jax.numpy as jnp

    def leaves(name, fill):
        return jax.tree_util.tree_map_with_path(
            lambda path, w: jnp.full_like(w, fill) if str(getattr(
                path[-1], "key", "")) == name else w, params)

    if what == "sink":
        return cfg, leaves("sink", -1e30)
    if what == "selection bias":
        return cfg, leaves("router_bias", 0.0)
    # A window wider than the prompt is no window.
    change = {"window": {"window": 64},
              "value scale": {"value_scale": 1.0},
              "partial rotary": {"rotary_dim": cfg.head_dim}}[what]
    return dataclasses.replace(cfg, **change), params


@pytest.mark.parametrize("what", ["sink", "window", "value scale",
                                  "partial rotary", "selection bias"])
def test_each_piece_changes_the_result_when_left_out(model, what):
    cfg, params = model
    tokens = _tokens(cfg, 37, seed=2)
    got, _, _ = _prefilled(cfg, params, tokens, 16)
    less_cfg, less_params = _without(cfg, params, what)
    less = _reference(less_cfg, less_params, tokens, [36])[0]
    assert np.abs(got - less).max() > 50 * TOL, what
    # And the program follows the piece: left out of both, they agree.
    both, _, _ = _prefilled(less_cfg, less_params, tokens, 16)
    assert np.abs(both - less).max() < TOL, what


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_whole(model):
    """Each of 16 chips holds one expert and computes its own; the sum of
    their outputs is the reference's uncut layer, and a token none of
    whose experts a share holds gets zero from it."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import mimo_v2_ref as ref
    from ray_tpu.ops import moe

    cfg, params = model
    seg = params["segments"][1]           # the four window expert layers
    held_first, held = cfg.held
    x = jax.random.normal(jax.random.key(3), (40, cfg.dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ seg["router"][0])
        weights = ref._route(scores, seg["router_bias"][0], cfg)
        # The tree holds experts 0-7; the uncut layer over those is the
        # reference's sum with every one of them held.
        want = sum(weights[:, e:e + 1] * ref._swiglu(
            x, seg["experts"]["w_gate"][0, e], seg["experts"]["w_up"][0, e],
            seg["experts"]["w_down"][0, e]) for e in range(held))
        idx, w = moe.route(x @ seg["router"][0], cfg.router(),
                           bias=seg["router_bias"][0])
        total = jnp.zeros_like(x)
        for e in range(held):
            one = {k: v[0, e:e + 1] for k, v in seg["experts"].items()}
            y, sizes = moe.held_experts_ffn(x, idx, w, one, (e, 1))
            total = total + y
            none = ~(idx == e).any(-1)
            assert not np.asarray(y)[np.asarray(none)].any()
            assert int(sizes[0]) == int((idx == e).sum())
    assert np.abs(np.asarray(total - want)).max() < TOL
    # Tokens route to absent experts too (8 of 16 are held).
    assert float((idx >= held).mean()) > 0.2


def test_the_sigmoid_router_chooses_by_score_plus_bias(model):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    router = moe.Router(experts=4, top_k=2, renormalise=True,
                        score="sigmoid")
    idx, w = moe.route(logits, router)
    assert idx.tolist() == [[0, 1]]
    g = 1 / (1 + np.exp(-np.asarray([2.0, 1.0])))
    assert np.allclose(np.asarray(w[0]), g / g.sum(), atol=1e-6)
    # The bias moves the CHOICE; the weights stay the scores'.
    idx, w = moe.route(logits, router, bias=jnp.asarray([0., 0., 0., 2.]))
    assert idx.tolist() == [[3, 0]]
    g = 1 / (1 + np.exp(-np.asarray([-1.0, 2.0])))
    assert np.allclose(np.asarray(w[0]), g / g.sum(), atol=1e-6)
    with pytest.raises(ValueError, match="score"):
        moe.route(logits, moe.Router(experts=4, top_k=1, score="tanh"))


def test_segments_and_shapes_follow_the_two_published_lists():
    from ray_tpu.models import mimo

    cfg = mimo.MimoConfig()
    assert len(cfg.layer_pattern) == len(cfg.moe_pattern) == 48
    assert [l for l in range(48) if cfg.kind(l) == "full"] == \
        [0, 5, 11, 17, 23, 29, 35, 41, 47]
    segs = cfg.segments()
    assert len(segs) == 17 and sum(s.layers for s in segs) == 48
    assert segs[0] == mimo.Segment("full", False, 1, 0)
    assert segs[1] == mimo.Segment("window", True, 4, 0)
    # 308.8 B parameters whole, as published (309B).
    assert abs(mimo.param_count(cfg) / 1e9 - 308.8) < 0.1
    cut = dataclasses.replace(cfg, n_layers=7, experts_held=(0, 16),
                              vocab_size=19072)
    assert [(s.kind, s.moe, s.layers) for s in cut.segments()] == [
        ("full", False, 1), ("window", True, 4), ("full", True, 1),
        ("window", True, 1)]
    assert abs(mimo.param_count(cut) / 1e9 - 3.43) < 0.005
    assert cut.rotary_dim == int(192 * 0.334) == 64
    with pytest.raises(ValueError, match="shorter"):
        dataclasses.replace(cfg, layer_pattern=(0, 1)).segments()
