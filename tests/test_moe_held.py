"""``ops/moe.py::held_experts_ffn`` where the work follows the pairs this
chip's experts own (PR 50): a chunk's call is cut to the held pairs, ``cap``
rows a pass in tiles that belong to one expert each, and held to a
loop-written float32 layer; a decode step's call lowers to the text it had.
The kernels (``ops/grouped_matmul.py``) run in the Pallas interpreter
here."""

import numpy as np
import pytest

# A chunk's shape at toy widths: 2,048 tokens choose 8 of 128 experts, 16
# of them held from expert 32 on; three layers stacked.
T, K, WIDTH, HELD, FIRST, D, M, LAYERS = 2048, 8, 128, 16, 32, 32, 16, 3


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _layer_by_loops(x, idx, w, ex, keep):
    """Every held (token, expert) pair, an expert at a time, float64."""
    y = np.zeros(x.shape, np.float64)
    sizes = np.zeros(HELD, np.int64)
    for e in range(HELD):
        for j in range(idx.shape[1]):
            rows = np.nonzero((idx[:, j] == FIRST + e) & keep)[0]
            h = _silu(x[rows] @ ex["w_gate"][e]) * (x[rows] @ ex["w_up"][e])
            y[rows] += w[rows, j][:, None] * (h @ ex["w_down"][e])
            sizes[e] += len(rows)
    return y, sizes


def _case(name):
    """``(logits, keep, stacked)`` of a case; the router is a sigmoid's
    top 8 of 128, renormalised."""
    rng = np.random.default_rng(7)
    logits = rng.normal(size=(T, WIDTH)).astype(np.float32)
    keep = np.ones(T, bool)
    mine = slice(FIRST, FIRST + HELD)
    if name == "every_token_chooses_held_experts":
        logits[:, mine] += 20.0
    elif name == "no_token_chooses_one":
        logits[:, mine] -= 20.0
    elif name == "a_keep_mask":
        keep = rng.random(T) < 0.6
    elif name == "sizes_are_a_bincount":
        logits[:, FIRST + 5] += 20.0        # one expert takes every token
    return logits, keep, name == "the_stack_with_a_traced_layer"


@pytest.mark.parametrize("name", [
    "balanced_is_one_pass",
    "every_token_chooses_held_experts",
    "no_token_chooses_one",
    "a_keep_mask",
    "the_stack_with_a_traced_layer",
    "sizes_are_a_bincount",
])
def test_the_held_pairs_alone_give_the_loop_written_layer(name):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    logits, keep, stacked = _case(name)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(T, D)).astype(np.float32)
    stack = {
        "w_gate": rng.normal(size=(LAYERS, HELD, D, M)) / np.sqrt(D),
        "w_up": rng.normal(size=(LAYERS, HELD, D, M)) / np.sqrt(D),
        "w_down": rng.normal(size=(LAYERS, HELD, M, D)) / np.sqrt(M),
    }
    stack = {n: w.astype(np.float32) for n, w in stack.items()}
    router = moe.Router(experts=WIDTH, top_k=K, renormalise=True,
                        score="sigmoid")
    cap, tile = moe.held_rows(T * K, HELD, WIDTH)
    # A balanced expert's 128 rows and a quarter more want a tile of 256;
    # a pass has one a held expert and a quarter more.
    assert (cap, tile) == (20 * 256, 256)

    which = 1
    experts = stack if stacked else {n: w[which] for n, w in stack.items()}

    @jax.jit
    def layer(x, logits, keep, experts, at):
        idx, w = moe.route(logits, router)
        y, sizes = moe.held_experts_ffn(
            x, idx, w, experts, (FIRST, HELD), keep=keep,
            layer=at if stacked else None, router=router)
        return y, sizes, idx, w

    with jax.default_matmul_precision("highest"):
        y, sizes, idx, w = layer(
            jnp.asarray(x), jnp.asarray(logits), jnp.asarray(keep),
            jax.tree.map(jnp.asarray, experts), jnp.int32(which))
    want, want_sizes = _layer_by_loops(
        x, np.asarray(idx), np.asarray(w),
        {n: s[which] for n, s in stack.items()}, keep)
    sizes = np.asarray(sizes)
    np.testing.assert_array_equal(sizes, want_sizes)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)
    held_pairs = int(want_sizes.sum())
    tiles = int((-(-want_sizes // tile)).sum())
    if name == "balanced_is_one_pass":
        assert 0 < tiles <= cap // tile
    elif name == "every_token_chooses_held_experts":
        assert held_pairs == T * K and tiles > 3 * cap // tile   # 4 passes
    elif name == "no_token_chooses_one":
        assert held_pairs == 0 and not np.asarray(y).any()
    elif name == "a_keep_mask":
        assert not np.asarray(y)[~keep].any()
    elif name == "sizes_are_a_bincount":
        chosen = np.asarray(idx).reshape(-1) - FIRST
        np.testing.assert_array_equal(
            sizes, np.bincount(chosen[(chosen >= 0) & (chosen < HELD)],
                               minlength=HELD))
        assert sizes[5] == T and tiles > cap // tile   # and a second pass


def test_padding_rows_are_selected_away_not_multiplied_by_zero(monkeypatch):
    """A tile's rows past its expert's pairs, and the tiles past the last
    expert's, are computed from whatever lies there; with NaN there a
    chunk's call must still be the held experts' part (the PR 36 finding,
    on the path a chunk takes since PR 50)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_matmul, moe

    real = grouped_matmul.add_rows

    def poisoned(rows, weights, token, tile_rows, live_tiles, tokens, tile):
        tiles = tile_rows.shape[0]
        live = (jnp.arange(tile)[None, :] < tile_rows[:, None]) \
            & (jnp.arange(tiles)[:, None] < live_tiles)
        rows = jnp.where(live.reshape(-1, 1), rows, jnp.nan)
        return real(rows, weights, token, tile_rows, live_tiles, tokens,
                    tile)

    monkeypatch.setattr(grouped_matmul, "add_rows", poisoned)
    logits, keep, _ = _case("balanced_is_one_pass")
    rng = np.random.default_rng(3)
    x = rng.normal(size=(T, D)).astype(np.float32)
    ex = {"w_gate": rng.normal(size=(HELD, D, M)).astype(np.float32) / 6,
          "w_up": rng.normal(size=(HELD, D, M)).astype(np.float32) / 6,
          "w_down": rng.normal(size=(HELD, M, D)).astype(np.float32) / 4}
    router = moe.Router(experts=WIDTH, top_k=K, renormalise=True,
                        score="sigmoid")
    idx, w = moe.route(jnp.asarray(logits), router)
    with jax.default_matmul_precision("highest"):
        y, sizes = moe.held_experts_ffn(
            jnp.asarray(x), idx, w, jax.tree.map(jnp.asarray, ex),
            (FIRST, HELD), router=router)
    cap, tile = moe.held_rows(T * K, HELD, WIDTH)
    assert 0 < int(sizes.sum()) < cap and (np.asarray(sizes) % tile).any()
    want, _ = _layer_by_loops(x, np.asarray(idx), np.asarray(w), ex, keep)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)


# The three cells that route: (tokens of a decode step, tokens of a chunk,
# top k, the router's width, held, model width, expert width, layers).
CELLS = {
    "deepseek-v2.longdocs_batch": (32, 2048, 6, 160, 40, 5120, 1536, 4),
    "command-a-plus.grounded_docs_batch": (32, 2048, 8, 128, 16, 4096, 4096,
                                           4),
    "mimo-v2.5.mixed_lengths_batch": (24, 2048, 8, 256, 16, 4096, 2048, 6),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_is_shapes_alone_and_a_decode_step_keeps_its_text(cell):
    """At the cell's served widths (shapes only: nothing is compiled) a
    decode step's call lowers to the text of the path every call took
    before PR 50, whose kernels ``moe_experts_roofline_pct.*`` finds by
    name; a chunk's names the rows it was cut to."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    slots, chunk, k, width, held, dim, mlp, layers = CELLS[cell]
    router = moe.Router(experts=width, top_k=k)
    assert slots * k in (192, 256)

    def args(tokens):
        s = jax.ShapeDtypeStruct
        experts = {"w_gate": s((layers, held, dim, mlp), jnp.bfloat16),
                   "w_up": s((layers, held, dim, mlp), jnp.bfloat16),
                   "w_down": s((layers, held, mlp, dim), jnp.bfloat16)}
        return (s((tokens, dim), jnp.bfloat16), s((tokens, k), jnp.int32),
                s((tokens, k), jnp.float32), experts, s((tokens,), bool),
                s((), jnp.int32))

    def before(x, idx, w, experts, keep, at):
        return moe._all_pairs(x, idx, w, experts, (0, held), keep, at)

    def layer(x, idx, w, experts, keep, at):
        return moe.held_experts_ffn(x, idx, w, experts, (0, held), keep=keep,
                                    layer=at, router=router)

    before.__name__ = "layer"
    assert moe.held_rows(slots * k, held, width) is None
    assert jax.jit(layer).lower(*args(slots)).as_text() == \
        jax.jit(before).lower(*args(slots)).as_text()
    cap, tile = moe.held_rows(chunk * k, held, width)
    assert (cap, tile) == {
        "deepseek-v2.longdocs_batch": (50 * 128, 128),
        "command-a-plus.grounded_docs_batch": (20 * 256, 256),
        "mimo-v2.5.mixed_lengths_batch": (20 * 128, 128)}[cell]
    text = jax.jit(layer).lower(*args(chunk)).as_text(debug_info=True)
    assert f"held_rows_{cap}" in text and "ragged_dot" not in text
    for kernel in ("moe_grouped_swiglu", "moe_grouped_matmul",
                   "moe_add_rows"):
        assert kernel in text
    # A wave of short prompts is a chunk by its shape too, once it is as
    # large; a prompt's last piece of 1,024 tokens or fewer keeps every
    # pair (its program would pay the kernels' set-up for one step in nine).
    assert moe.held_rows(8 * 256 * k, held, width) is not None
    assert moe.held_rows(1024 * k, held, width) is None
    assert moe.held_rows(256 * k, held, width) is None


@pytest.mark.parametrize("name", ["deepseek", "mimo", "cohere2_moe"])
def test_a_models_chunk_goes_the_held_pairs_way_and_gives_what_it_gave(
        name, monkeypatch):
    """Each model hands ``held_experts_ffn`` its router: a chunk-sized call
    of its feed-forward at the debug widths (4,096 tokens x 3 of 16 experts,
    8 held: twenty tiles of 512) takes ``_held_pairs`` and agrees with the
    same call made to keep every pair."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    mod = importlib.import_module(f"ray_tpu.models.{name}")
    dec = importlib.import_module(f"ray_tpu.models.{name}_decode")
    cfg = mod.PRESETS["debug"]
    params = mod.init_params(cfg, jax.random.key(5))
    if name == "deepseek":
        stacked, seg = params["moe"], None
    else:
        at = next(i for i, s in enumerate(cfg.segments())
                  if getattr(s, "moe", True) and s.layers > 1)
        stacked, seg = params["segments"][at], cfg.segments()[at]
    which = 1
    layer = {k: jax.tree.map(lambda a: a[which], v)
             for k, v in stacked.items() if k != "experts"}
    layer.update(experts=stacked["experts"],
                 expert_layer=jnp.asarray(which, jnp.int32))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 2048, cfg.dim)), jnp.float32)
    keep = jnp.asarray(rng.random((2, 2048)) < 0.9)
    assert moe.held_rows(4096 * cfg.top_k, cfg.held[1],
                         cfg.n_routed_experts) == (10240, 512)

    def feed_forward():
        with jax.default_matmul_precision("highest"):
            if name == "deepseek":
                return dec._moe_ffn(layer, x, cfg, keep)
            if name == "mimo":
                return dec._ffn(layer, x, cfg, seg, keep)
            return dec._ffn(layer, x, cfg, keep)

    calls = []
    real = moe._held_pairs
    monkeypatch.setattr(moe, "_held_pairs",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    got, got_stats = feed_forward()
    assert calls == [1]
    monkeypatch.setattr(moe, "held_rows", lambda *a: None)
    want, want_stats = feed_forward()
    assert calls == [1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_stats),
                                  np.asarray(want_stats))
