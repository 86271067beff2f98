"""``ops/moe.py::held_experts_ffn`` over both FORMS of expert (a SwiGLU of
three leaves; Nemotron-H's squared ReLU of two, whose width in and out is a
latent one) in both WAYS (``_all_pairs``, ragged; ``_held_pairs``, the
held pairs in tiles), against a dense loop over every held pair; and
Nemotron-H's shape, top 22 of 512 with 128 held, at a chunk's and a decode
step's pair counts. The kernels run in the Pallas interpreter here."""

import numpy as np
import pytest

D, M = 32, 24


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _dense_loop(x, idx, w, ex, keep, first, held):
    """Every held (token, expert) pair, an expert at a time, float64."""
    y = np.zeros(x.shape, np.float64)
    sizes = np.zeros(held, np.int64)
    for e in range(held):
        for j in range(idx.shape[1]):
            rows = np.nonzero((idx[:, j] == first + e) & keep)[0]
            up = x[rows].astype(np.float64) @ ex["w_up"][e]
            if "w_gate" in ex:
                h = _silu(x[rows].astype(np.float64) @ ex["w_gate"][e]) * up
            else:
                h = np.maximum(up, 0.0) ** 2
            y[rows] += w[rows, j][:, None] * (h @ ex["w_down"][e])
            sizes[e] += len(rows)
    return y, sizes


def _experts(rng, held, form, layers=None):
    lead = (held,) if layers is None else (layers, held)
    names = ("w_gate", "w_up") if form == "swiglu" else ("w_up",)
    ex = {n: rng.normal(size=lead + (D, M)) / np.sqrt(D) for n in names}
    ex["w_down"] = rng.normal(size=lead + (M, D)) / np.sqrt(M)
    return {n: w.astype(np.float32) for n, w in ex.items()}


def _run(tokens, top_k, width, first, held, form, way, layers=None,
         layer=None, keep_share=1.0, seed=0):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(tokens, D)).astype(np.float32)
    logits = rng.normal(size=(tokens, width)).astype(np.float32)
    keep = rng.random(tokens) < keep_share
    bias = (rng.normal(size=(width,)) * 0.1).astype(np.float32)
    router = moe.Router(experts=width, top_k=top_k, renormalise=True,
                        scale=5.0, score="sigmoid")
    idx, w = moe.route(jnp.asarray(logits), router, bias=jnp.asarray(bias))
    ex = _experts(rng, held, form, layers)
    args = (jnp.asarray(x), idx, w, {n: jnp.asarray(a) for n, a in
                                     ex.items()}, (first, held),
            jnp.asarray(keep), None if layer is None else jnp.int32(layer))
    if way == "all_pairs":
        y, sizes = moe._all_pairs(*args)
    elif way == "held_pairs":
        plan = moe.held_rows(tokens * top_k, held, width) or (
            held * 2 * 8, 8)
        y, sizes = moe._held_pairs(
            *args, *plan, matmuls=moe._tiled if form == "swiglu"
            else moe._tiled_relu2)
    else:
        y, sizes = moe.held_experts_ffn(*args, router=router)
    mine = ex if layer is None else {n: a[layer] for n, a in ex.items()}
    want, want_sizes = _dense_loop(x, np.asarray(idx), np.asarray(w), mine,
                                   keep, first, held)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    assert np.array_equal(np.asarray(sizes), want_sizes)
    return np.asarray(sizes)


@pytest.mark.parametrize("way", ["all_pairs", "held_pairs"])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_both_forms_both_ways_are_the_dense_loop(form, way):
    _run(256, 3, 16, 4, 8, form, way, keep_share=0.8)


@pytest.mark.parametrize("way", ["all_pairs", "held_pairs"])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_a_stack_with_a_traced_layer(form, way):
    _run(128, 3, 16, 0, 8, form, way, layers=3, layer=1, seed=3)


def test_top_22_of_512_with_128_held_at_a_chunks_pair_count():
    from ray_tpu.ops import moe

    # A 2,048-token chunk: 45,056 pairs, a quarter of them held; one pass
    # of 160 tiles of 128 rows, as at the served size.
    assert moe.held_rows(2048 * 22, 128, 512) == (20480, 128)
    sizes = _run(2048, 22, 512, 0, 128, "relu2", "chosen", seed=5)
    # The selection bias leaves the load uneven: some expert's rows span
    # several tiles and some hold none.
    assert 10000 < sizes.sum() < 12500 and sizes.max() > 128
    assert sizes.min() == 0


def test_top_22_of_512_with_128_held_at_a_decode_steps_pair_count():
    from ray_tpu.ops import moe

    # 96 slots: 2,112 pairs over MANY small held experts take tiles of 64
    # rows, a tile a held expert and a quarter more; the other models'
    # decode steps (192-256 pairs, 16-40 held) keep every pair.
    assert moe.held_rows(96 * 22, 128, 512) == (160 * 64, 64)
    assert moe.held_rows(16 * 22, 128, 512) is None
    assert moe.held_rows(32 * 6, 40, 160) is None
    assert moe.held_rows(1024 * 6, 40, 160) is None
    assert moe.held_rows(24 * 8, 16, 128) is None
    sizes = _run(96, 22, 512, 0, 128, "relu2", "chosen", keep_share=0.9,
                 seed=6)
    assert 350 < sizes.sum() < 650


def test_the_form_is_read_off_the_leaves():
    from ray_tpu.ops import moe

    assert moe._gated({"w_gate": 0, "w_up": 0, "w_down": 0})
    assert not moe._gated({"w_up": 0, "w_down": 0})
