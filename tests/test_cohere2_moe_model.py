"""Command A+ (``models/cohere2_moe.py``, ``models/cohere2_moe_decode.py``)
against its plain reference (``benchmarks/reference/cohere2_moe_ref.py``) at
the debug preset, in float32 on the CPU: whole prefill, chunked prefill and
decode through the engine's two kinds of page, LOGITS compared, over prompts
that cross the window (12), a page (4) and a chunk (32); each of the
model's own pieces moves the result when the reference leaves it out or
alters it; the eight shares of an expert layer, with the shared experts
counted once, add up to the uncut layer."""

import dataclasses

import numpy as np
import pytest

TOL = 2e-4
# What a piece left out or altered has to move the logits by, at least.
MOVES = 1e-2


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import cohere2_moe

    cfg = cohere2_moe.PRESETS["debug"]
    return cfg, cohere2_moe.init_params(cfg, jax.random.key(7))


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

    from ray_tpu.models import cohere2_moe_decode as md

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
    from benchmarks.reference import cohere2_moe_ref

    return np.asarray(cohere2_moe_ref.logits(params, tokens, cfg,
                                             rows=rows))


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
    built by ``live_page_view`` from the window's last pages only: the
    steps pass a page's edge and a window page's release (positions 32 and
    36 at pages of 4 under a window of 12)."""
    import jax.numpy as jnp

    from ray_tpu.models import cohere2_moe_decode as md

    cfg, params = model
    T, n, steps = 4, 30, 8
    tokens = _tokens(cfg, n + steps, seed=1)
    _, pool, ids = _prefilled(cfg, params, tokens[:n], 16)
    want = _reference(cfg, params, tokens, list(range(n, n + steps)))
    table = np.zeros((2, 16), np.int32)
    table[1, :len(ids)] = ids
    firsts = set()
    for j in range(steps):
        pos = n + j
        first = max(0, pos - cfg.window + 1) // T
        firsts.add(first)
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
        assert float(stats[0]) <= 4 * cfg.top_k     # slot 1's pairs only
    assert len(firsts) > 1                          # a page was released


def test_the_engine_serves_the_references_tokens(model):
    """Through ``DecodeEngine``: admission, chunks, window pages freed as
    they are passed in prefill and in decode, four prompts at once."""
    from benchmarks.reference import cohere2_moe_ref
    from ray_tpu.models import cohere2_moe_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    eng = DecodeEngine(params, cfg, slots=4, capacity=256, page_tokens=4,
                       prefill_chunk_tokens=32, model=cohere2_moe_decode,
                       step_timeline=0, metrics_enabled=False,
                       trace_spans=False)
    prompts = [_tokens(cfg, n, seed=n).tolist() for n in (50, 7, 100, 33)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (20, 30, 10, 5))]
    for _ in range(300):
        eng.step()
        if all(r.done.is_set() for r in reqs):
            break
    margins = cohere2_moe_ref.served_token_margins(
        eng.params, cfg, prompts, [r.output for r in reqs])
    assert len(margins) == 65 and max(margins) < TOL
    assert eng.stats()["pages_in_use_by_kind"] == {"full": 0, "window": 0}
    eng.shutdown()


# ---------------------------------------------- each piece moves the result


def _altered_reference(monkeypatch, cfg, params, what):
    """The reference with one piece left out or altered: ``(cfg, params)``
    to hand it, under patches of its own functions where no key of the
    config says the piece. A config that differs in ``max_seq_len`` only
    keeps a patched trace apart from the jit cache's sound ones."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import cohere2_moe_ref as ref

    fresh = dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1 + [
        "sequential block", "no mean in LayerNorm", "rotary in a full layer",
        "no rotary in a window layer", "half-split pairs", "window + 1",
        "window - 1", "sum for average", "no renormalisation",
        "logit_scale"].index(what))
    if what == "sequential block":
        def layer(seg, l, x, cfg, window, bits):
            with jax.default_matmul_precision("highest"):
                x = x + ref._attention(seg, l, ref._layer_norm(
                    x, seg["norm"][l], cfg.norm_eps), cfg, window, bits)
                return x + ref._feed_forward(seg, l, ref._layer_norm(
                    x, seg["norm"][l], cfg.norm_eps), cfg, bits)

        monkeypatch.setattr(ref, "_layer", layer)
    elif what == "no mean in LayerNorm":
        monkeypatch.setattr(
            ref, "_layer_norm", lambda x, scale, eps: x * jax.lax.rsqrt(
                jnp.mean(x * x, -1, keepdims=True) + eps) * scale)
    elif what == "rotary in a full layer":
        attention = ref._attention

        def rotated(seg, l, h, cfg, window, bits):
            # A window wider than the prompt masks nothing and rotates.
            return attention(seg, l, h, cfg if window else
                             dataclasses.replace(cfg, window=10 ** 6),
                             True, bits)

        monkeypatch.setattr(ref, "_attention", rotated)
    elif what == "no rotary in a window layer":
        monkeypatch.setattr(ref, "_rope", lambda x, theta: x)
    elif what == "half-split pairs":
        def half_split(x, theta):
            s, d = x.shape[0], x.shape[-1]
            inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
            ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
                * jnp.asarray(inv)
            cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            a, b = x[..., :d // 2], x[..., d // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                                   -1)

        monkeypatch.setattr(ref, "_rope", half_split)
    elif what in ("window + 1", "window - 1"):
        fresh = dataclasses.replace(
            fresh, window=cfg.window + (1 if what[-3] == "+" else -1))
    elif what == "sum for average":
        params = jax.tree_util.tree_map_with_path(
            lambda path, w: w * cfg.n_shared_experts if [
                str(getattr(p, "key", "")) for p in path][-2:]
            == ["shared", "w_down"] else w, params)
    elif what == "no renormalisation":
        fresh = dataclasses.replace(fresh, norm_topk_prob=False)
    elif what == "logit_scale":
        fresh = dataclasses.replace(fresh, logit_scale=1.0)
    return fresh, params


@pytest.mark.parametrize("what", [
    "sequential block", "no mean in LayerNorm", "rotary in a full layer",
    "no rotary in a window layer", "half-split pairs", "window + 1",
    "window - 1", "sum for average", "no renormalisation", "logit_scale"])
def test_each_piece_moves_the_result_when_left_out_or_altered(
        model, monkeypatch, what):
    cfg, params = model
    tokens = _tokens(cfg, 37, seed=2)
    got, _, _ = _prefilled(cfg, params, tokens, 16)
    less_cfg, less_params = _altered_reference(monkeypatch, cfg, params,
                                               what)
    less = _reference(less_cfg, less_params, tokens, [36])[0]
    assert np.abs(got - less).max() > MOVES, what
    # The sound reference, at the same sizes, agrees.
    monkeypatch.undo()
    sound = _reference(cfg, params, tokens, [36])[0]
    assert np.abs(got - sound).max() < TOL, what


@pytest.mark.parametrize("change", [{"window": 13}, {"window": 11},
                                    {"norm_topk_prob": False},
                                    {"logit_scale": 1.0}])
def test_the_program_follows_its_config(model, change):
    """Where a key of the config says the piece, program and reference
    altered alike agree again."""
    cfg, params = model
    other = dataclasses.replace(cfg, **change)
    tokens = _tokens(cfg, 37, seed=2)
    got, _, _ = _prefilled(other, params, tokens, 16)
    assert np.abs(got - _reference(other, params, tokens, [36])[0]
                  ).max() < TOL


# ------------------------------------------------------- the chip's share


def test_the_eight_shares_of_a_layer_add_up_to_the_whole():
    """Each of 8 chips holds two of 16 routed experts and all of the
    shared ones: the routed parts of the eight shares (the program's own
    ``_ffn``) plus the shared experts' average counted ONCE are the
    reference's uncut layer; a token none of whose experts a share holds
    gets the shared part only from it."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import cohere2_moe_ref as ref
    from ray_tpu.models import cohere2_moe
    from ray_tpu.models import cohere2_moe_decode as md

    whole = dataclasses.replace(cohere2_moe.PRESETS["debug"],
                                experts_held=None)
    params = cohere2_moe.init_params(whole, jax.random.key(11))
    seg = params["segments"][0]
    assert seg["experts"]["w_gate"].shape[:2] == (3, 16)
    h = jax.random.normal(jax.random.key(3), (1, 40, whole.dim),
                          jnp.float32)
    keep = jnp.ones((1, 40), bool)
    with jax.default_matmul_precision("highest"):
        want = ref._feed_forward(seg, 1, h[0], whole, None)
        shared = md._swiglu({k: v[1] for k, v in seg["shared"].items()},
                            h) / whole.n_shared_experts
        total, pairs = shared, 0.0
        for chip in range(8):
            share = dataclasses.replace(whole, experts_held=(2 * chip, 2))
            layer = {"router": seg["router"][1],
                     "shared": {k: v[1] for k, v in seg["shared"].items()},
                     "experts": {k: v[:, 2 * chip:2 * chip + 2]
                                 for k, v in seg["experts"].items()},
                     "expert_layer": jnp.asarray(1, jnp.int32)}
            f, stats = md._ffn(layer, h, share, keep)
            routed = f - shared
            total = total + routed
            pairs += float(stats[0])
            _, idx = jax.lax.top_k(jax.nn.sigmoid(
                h[0] @ seg["router"][1]), whole.top_k)
            none = ~((idx // 2) == chip).any(-1)
            assert np.abs(np.asarray(routed[0])[np.asarray(none)]
                          ).max() < 1e-6
    assert np.abs(np.asarray(total[0] - want)).max() < TOL
    # Every (token, expert) pair fell to exactly one share.
    assert pairs == 40 * whole.top_k


def test_segments_shapes_and_counts_follow_the_published_config():
    from ray_tpu.models import cohere2_moe

    cfg = cohere2_moe.Cohere2MoeConfig()
    assert len(cfg.layer_types) == 32
    assert [l for l in range(32) if cfg.kind(l) == "full"] == \
        list(range(3, 32, 4))
    segs = cfg.segments()
    assert len(segs) == 16 and sum(s.layers for s in segs) == 32
    assert segs[0] == cohere2_moe.Segment("window", 3, 0)
    assert segs[1] == cohere2_moe.Segment("full", 1, 0)
    assert segs[2] == cohere2_moe.Segment("window", 3, 3)
    # 218.25 B parameters whole, as published (218B).
    assert abs(cohere2_moe.param_count(cfg) / 1e9 - 218.25) < 0.01
    cut = dataclasses.replace(cfg, n_layers=4, experts_held=(0, 16),
                              vocab_size=32768)
    assert [(s.kind, s.layers) for s in cut.segments()] == [
        ("window", 3), ("full", 1)]
    # ISSUE 49's arithmetic: 1,149.8 M a layer, 4.733 B in all.
    assert abs(cohere2_moe.param_count(cut) / 1e9 - 4.733) < 0.001
    assert cfg.router().score == "sigmoid" and cfg.router().renormalise
    with pytest.raises(ValueError, match="shorter"):
        dataclasses.replace(cfg, layer_types=("full_attention",)).segments()
