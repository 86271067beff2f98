"""DeepSeek-V2 behind the decode engine's model seam, at a small size on
the CPU, seeded random weights, float32: the engine's streams against the
plain reference's full forward (``benchmarks/reference/deepseek_v2_ref.py``,
which shares no code with the program), the two formulations of the latent
attention against each other, YaRN against a transcription of the
published formulas, group-limited dropless routing against a loop, and the
chips' shares of an expert layer against the uncut layer."""

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Two float32 programs that sum in different orders; a wrong page, a
# wrong position or a dropped expert moves logits by tenths or units.
MARGIN = 2e-3
LOGITS_ATOL = 2e-3


@pytest.fixture(scope="module")
def model():
    import jax

    from ray_tpu.models import deepseek

    cfg = deepseek.PRESETS["debug"]
    return cfg, deepseek.init_params(cfg, jax.random.key(7))


def _engine(model, **kw):
    from ray_tpu.models import deepseek_decode
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = model
    kw.setdefault("slots", 4)
    kw.setdefault("capacity", 512)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("prefix_pool_entries", 0)
    return DecodeEngine(params, cfg, model=deepseek_decode, **kw)


def _prompt(rng, n, vocab=128):
    return [int(t) for t in rng.integers(0, vocab, n)]


def _drive(eng, reqs, steps=2000):
    for _ in range(steps):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError("requests not done")


def _margins(model, prompts, reqs):
    from benchmarks.reference import deepseek_v2_ref

    cfg, params = model
    for r in reqs:
        r.raise_for_status()
    return deepseek_v2_ref.served_token_margins(
        params, cfg, prompts, [list(r.output) for r in reqs])


# ------------------------------------- (a) engine streams vs the reference


def test_whole_prefill_and_decode_match_the_reference(model):
    rng = np.random.default_rng(0)
    eng = _engine(model)
    prompts = [_prompt(rng, n) for n in (5, 23, 60)]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng, reqs)
    assert max(_margins(model, prompts, reqs)) < MARGIN
    assert eng.prefill_chunks == 0


def test_chunked_prefill_across_chunks_matches_the_reference(model):
    rng = np.random.default_rng(1)
    eng = _engine(model, prefill_chunk_tokens=32)
    prompts = [_prompt(rng, 100), _prompt(rng, 77)]   # 4 and 3 chunks
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    _drive(eng, reqs)
    assert eng.prefill_chunks >= 7
    assert max(_margins(model, prompts, reqs)) < MARGIN


def test_decode_on_more_than_one_rung_matches_the_reference(model):
    rng = np.random.default_rng(2)
    eng = _engine(model, step_timeline=512)
    assert eng._view_ladder[:2] == (64, 128)
    short = [_prompt(rng, 9)]
    first = [eng.submit(short[0], max_new_tokens=3)]
    _drive(eng, first)
    # Three contexts of ~30 pages each: past the 64-row rung.
    prompts = [_prompt(rng, n) for n in (118, 121, 125)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    _drive(eng, reqs)
    rungs = {r["view_pages"] for r in eng.steplog.dump()["rows"]
             if r.get("view_pages")}
    assert {64, 128} <= rungs
    assert max(_margins(model, short + prompts, first + reqs)) < MARGIN


def test_a_preempted_request_resumes_and_matches_the_reference(model):
    rng = np.random.default_rng(3)
    # 40 pages of 4 tokens: two contexts of ~84 tokens do not both fit
    # once they grow, so the younger is preempted and comes back.
    eng = _engine(model, pool_pages=44, prefill_chunk_tokens=32)
    prompts = [_prompt(rng, 80), _prompt(rng, 76)]
    reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
    _drive(eng, reqs)
    assert eng.preempted >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    assert max(_margins(model, prompts, reqs)) < MARGIN


def test_a_prefix_hit_splices_latent_pages_and_matches_the_reference(model):
    rng = np.random.default_rng(4)
    eng = _engine(model, prefix_pool_entries=8, prefix_match_min_tokens=8)
    base = _prompt(rng, 64)
    prompts = [base + _prompt(rng, 9), base + _prompt(rng, 13)]
    reqs = []
    for p in prompts:                      # one after the other
        reqs.append(eng.submit(p, max_new_tokens=5))
        _drive(eng, reqs)
    assert reqs[1].prefix_len >= 32        # pages of the first were reused
    assert max(_margins(model, prompts, reqs)) < MARGIN


def test_the_engine_refuses_a_mesh_whose_program_the_model_lacks(model):
    with pytest.raises(ValueError, match="shard_decode_state"):
        _engine(model, mesh_shape=(1, 2))


def test_the_step_log_carries_the_expert_counters(model):
    rng = np.random.default_rng(5)
    eng = _engine(model, step_timeline=64)
    reqs = [eng.submit(_prompt(rng, 12), max_new_tokens=4)
            for _ in range(3)]
    _drive(eng, reqs)
    launches = [s for r in eng.steplog.dump()["rows"]
                for s in r.get("slices", [])
                if s["name"] == "launch" and s.get("program") == "decode"]
    assert launches
    cfg = model[0]
    for s in launches:
        # At most top_k pairs a stepping slot a layer; the experts hit
        # and the fullest one are bounded by the pairs.
        assert 0 <= s["moe_pairs"] <= (s["batch"] * cfg.top_k
                                       * cfg.n_moe_layers)
        assert s["moe_experts_hit"] <= s["moe_pairs"]
        assert s["moe_max_load"] <= s["batch"] * cfg.n_moe_layers
    assert sum(s["moe_pairs"] for s in launches) > 0


def test_the_counters_ride_with_the_ids_and_equal_a_direct_calls(model):
    """The decode returns ids, not logits, and the three ``STEP_STATS``
    come in the same small vector: each step's ``launch`` row and its
    ``sample_emit`` slice (the annotation the trace readers find) read
    what ``paged_decode_step`` itself counts on the same arguments, and
    the step's one fetch is 4 bytes a slot, a counter and the step."""
    import jax

    from ray_tpu.models import deepseek_decode as dd
    from ray_tpu.serve.decode import _pool_of

    cfg = model[0]
    rng = np.random.default_rng(9)
    eng = _engine(model, step_timeline=64)
    direct = jax.jit(dd.paged_decode_step, static_argnums=(5,))
    decode, counted = eng._decode, []

    def spied(params, cache, state, view, temps):
        logits, _, _, stats = direct(
            params, _pool_of(cache), view, cache["length"],
            state[:eng.slots], cfg)
        out, cache = decode(params, cache, state, view, temps)
        got = np.asarray(out)
        assert got.shape == (eng.slots + len(dd.STEP_STATS) + 1,)
        assert np.array_equal(got[:eng.slots],
                              np.asarray(logits).argmax(-1))
        counted.append([int(v) for v in np.asarray(stats)])
        return out, cache

    eng._decode = spied
    reqs = [eng.submit(_prompt(rng, n), max_new_tokens=5)
            for n in (12, 7, 20)]
    _drive(eng, reqs)
    rows = [r for r in eng.steplog.dump()["rows"] if any(
        s["name"] == "launch" and s.get("program") == "decode"
        for s in r["slices"])]
    assert len(rows) == len(counted) >= 4
    for row, want in zip(rows, counted):
        by = {}  # the decode's slices, the first ``sample_emit`` of the
        for s in row["slices"]:  # loop: a finish re-opens it bare
            if s.get("program", "decode") == "decode":
                by.setdefault(s["name"], s)
        for name in ("launch", "sample_emit"):
            assert [by[name][k] for k in dd.STEP_STATS] == want, name
        assert by["fetch"]["bytes"] == 4 * (eng.slots + 3 + 1)
    assert sum(w[0] for w in counted) > 0


# ------------------------- (b) absorbed decode = up-projected prefill


def test_absorbed_decode_equals_up_projected_prefill_on_one_cache(model):
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_decode as dd

    cfg, params = model
    rng = np.random.default_rng(6)
    T, n = 4, 37
    pool = dd.init_page_pool(cfg, 32, T)
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :n] = _prompt(rng, n)
    bt = np.arange(1, 17, dtype=np.int32)[None]
    _, pool = dd.paged_prefill(params, jnp.asarray(tokens), pool,
                               jnp.asarray(bt), cfg,
                               jnp.asarray([n], jnp.int32))
    nxt = jnp.asarray([[int(rng.integers(0, 128))]], jnp.int32)
    # The same token at position n, through both formulations, each on
    # its own copy of the cache.
    up, pool_up = dd.paged_prefill_suffix(
        params, nxt, dict(pool), jnp.asarray(bt), cfg,
        jnp.asarray([n], jnp.int32), jnp.asarray([n + 1], jnp.int32))
    view = dd.live_page_view(bt, np.asarray([-(-(n + 1) // T)]), 64)
    ab, pool_ab, lens, stats = dd.paged_decode_step(
        params, dict(pool), jnp.asarray(view), jnp.asarray([n], jnp.int32),
        nxt[0], cfg)
    assert int(lens[0]) == n + 1
    np.testing.assert_allclose(np.asarray(ab), np.asarray(up),
                               atol=LOGITS_ATOL)
    # Both stored the same latent row for the new token.
    np.testing.assert_allclose(np.asarray(pool_ab["latent"]),
                               np.asarray(pool_up["latent"]), atol=1e-5)
    assert pool["latent"].shape[-1] == cfg.latent_row == 128
    assert float(stats[0]) <= cfg.top_k * cfg.n_moe_layers


def _two_slots_of_four(model, T=4):
    """Slots 0 and 2 of four prefilled (37 and 9 tokens), 1 and 3 empty:
    ``(pool, tables, counts, lengths, tokens)`` for one decode step."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_decode as dd

    cfg, params = model
    rng = np.random.default_rng(8)
    pool = dd.init_page_pool(cfg, 32, T)
    lengths = np.asarray([37, 0, 9, 0], np.int32)
    tables = np.zeros((4, 16), np.int32)
    tables[0], tables[2] = np.arange(1, 17), np.arange(17, 33)
    for slot in (0, 2):
        n = int(lengths[slot])
        prompt = np.zeros((1, 64), np.int32)
        prompt[0, :n] = _prompt(rng, n)
        _, pool = dd.paged_prefill(params, jnp.asarray(prompt), pool,
                                   jnp.asarray(tables[slot:slot + 1]), cfg,
                                   jnp.asarray([n], jnp.int32))
    counts = np.where(lengths > 0, lengths // T + 1, 0)
    tokens = jnp.asarray(rng.integers(0, 128, 4), jnp.int32)
    return pool, tables, counts, jnp.asarray(lengths), tokens


def test_a_slot_with_no_row_and_the_rungs_padding_change_no_other_slots_logits(
        model):
    """The kernel reads a slot's live pages and nothing else: with NaN in
    the scratch page (what the rows that pad a group, the lists of nobody
    and a slot that owns no row all name), the stepping slots' logits are
    bit for bit what a clean scratch page gives, the others' are finite,
    and each stepping slot reads as it does alone in the view."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_decode as dd

    cfg, params = model
    pool, tables, counts, lengths, tokens = _two_slots_of_four(model)
    # 10 and 3 pages in two groups of 16, then two lists of nobody.
    view = dd.live_page_view(tables, counts, 64)
    assert (view[1].reshape(-1, 16)[:, 0] == [0, 2, -1, -1]).all()
    assert (view[0] == 0).sum() == 64 - 13

    def step(pool, counts):
        logits, *_ = dd.paged_decode_step(
            params, dict(pool), jnp.asarray(
                dd.live_page_view(tables, counts, 64)), lengths, tokens, cfg)
        return np.asarray(logits)

    clean = step(pool, counts)
    poisoned = step({"latent": pool["latent"].at[:, 0].set(jnp.nan)}, counts)
    assert np.isfinite(poisoned).all()
    assert np.array_equal(poisoned[[0, 2]], clean[[0, 2]])
    for slot in (0, 2):
        alone = step(pool, np.where(np.arange(4) == slot, counts, 0))
        np.testing.assert_allclose(clean[slot], alone[slot], atol=1e-5)
        assert np.abs(clean[slot] - clean[2 - slot]).max() > 1e-2


def _avals(jaxpr):
    """Every value of ``jaxpr`` and of the jaxprs inside its equations
    (the layer scans, the kernel's body)."""
    import jax.extend.core as jex

    def inside(x):
        if isinstance(x, jex.ClosedJaxpr):
            yield x.jaxpr
        elif isinstance(x, jex.Jaxpr):
            yield x
        elif isinstance(x, (tuple, list)):
            for y in x:
                yield from inside(y)

    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        yield None, v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn, v.aval
        for value in eqn.params.values():
            for sub in inside(value):
                yield from _avals(sub)


def test_the_decode_step_holds_no_copy_of_the_views_pages(model):
    """``paged_decode_step`` over a view of MORE rows than the pool has
    pages: nothing in its jaxpr, the layer scans and the kernel's body
    included, is as large as ``view rows x T x latent_row`` (the parent's
    ``flat[base + pages]``; the pool itself is smaller here), and each of
    the two layer loops calls the kernel once, under ``latent_attn``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_decode as dd
    from ray_tpu.ops import paged_decode_attention as pda

    cfg, params = model
    pool, tables, counts, lengths, tokens = _two_slots_of_four(model)
    rows, T = 128, pool["latent"].shape[2]
    copy = rows * T * cfg.latent_row
    assert pool["latent"].size < copy
    view = jnp.asarray(dd.live_page_view(tables, counts, rows))
    jaxpr = jax.make_jaxpr(
        lambda pool, view, lengths, tokens: dd.paged_decode_step(
            params, pool, view, lengths, tokens, cfg))(
                pool, view, lengths, tokens).jaxpr
    seen = list(_avals(jaxpr))
    largest = max(int(np.prod(a.shape)) for _, a in seen
                  if hasattr(a, "shape"))
    assert largest == pool["latent"].size < copy
    kernels = [e for e, _ in seen if e is not None
               and e.primitive.name == "pallas_call"]
    assert len({id(e) for e in kernels}) == 2
    for e in kernels:
        assert e.params["name"] == pda.NAME
        assert "latent_attn" in str(e.source_info.name_stack)
        # ONE pool handed in: the flat latent leaf, keys and values both.
        flat = (cfg.n_layers * 33, T, cfg.latent_row)
        assert [v.aval.shape for v in e.invars
                if v.aval.shape[1:] == flat[1:] and v.aval.dtype
                == pool["latent"].dtype and v.aval.shape[0] > 4] == [flat]
    assert not any("latent_gather" in str(e.source_info.name_stack)
                   for e, _ in seen if e is not None)


# ----------------------------------------------------------- (c) YaRN


def _published_yarn(dim, base, factor, original, beta_fast, beta_slow,
                    mscale, mscale_all_dim, positions):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache`` and its
    helpers, transcribed line by line with Python's ``math``."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    inv_freq = []
    for i in range(dim // 2):
        freq_extra = 1.0 / (base ** (2 * i / dim))
        freq_inter = 1.0 / (factor * base ** (2 * i / dim))
        hi = high + 0.001 if low == high else high
        ramp = min(1.0, max(0.0, (i - low) / (hi - low)))
        inv_freq_mask = 1.0 - ramp
        inv_freq.append(freq_inter * (1 - inv_freq_mask)
                        + freq_extra * inv_freq_mask)
    ratio = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    cos = [[math.cos(p * f) * ratio for f in inv_freq] for p in positions]
    sin = [[math.sin(p * f) * ratio for f in inv_freq] for p in positions]
    return np.asarray(inv_freq), np.asarray(cos), np.asarray(sin), \
        get_mscale(factor, mscale_all_dim)


def test_yarn_tables_match_the_published_formulas_past_the_trained_window():
    import jax.numpy as jnp

    from ray_tpu.models.deepseek import DeepseekConfig
    from ray_tpu.ops import rotary

    cfg = DeepseekConfig()      # the published numbers
    positions = [0, 1, 4095, 4096, 5000, 16383, 100000]
    inv, cos, sin, m = _published_yarn(64, 10000.0, 40.0, 4096, 32.0, 1.0,
                                       0.707, 0.707, positions)
    got = np.asarray(cfg.inv_freq())
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    # The fast dims keep their frequency, the slow ones are divided by 40.
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        1.0 / (40 * 10000 ** (62 / 64)), rel=1e-6)
    c, s = rotary.rope_at(jnp.asarray(positions), cfg.inv_freq(),
                          cfg.rope_scale)
    # float32 angles of ~1e5 radians carry ~1e-2 of absolute error.
    np.testing.assert_allclose(np.asarray(c), cos, atol=2e-2)
    np.testing.assert_allclose(np.asarray(c)[:5], cos[:5], atol=1e-3)
    np.testing.assert_allclose(np.asarray(s)[:5], sin[:5], atol=1e-3)
    assert cfg.rope_scale == pytest.approx(1.0)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)


def test_interleaved_rotation_keeps_dot_products():
    import jax.numpy as jnp

    from ray_tpu.ops import rotary

    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 8)).astype(np.float32)
    inv = jnp.asarray([1.0, 0.3, 0.1, 0.01])
    def rot(x, p, inter):
        c, s = rotary.rope_at(jnp.asarray([p]), inv)
        return np.asarray(rotary.rotate_pairs(jnp.asarray(x)[None], c, s,
                                              interleaved=inter))[0]
    # The published pairing (2i, 2i + 1), by hand.
    def by_hand(x, p):
        out = np.zeros(8, np.float32)
        for i, f in enumerate(np.asarray(inv)):
            a, b = x[2 * i], x[2 * i + 1]
            out[2 * i] = a * math.cos(p * f) - b * math.sin(p * f)
            out[2 * i + 1] = b * math.cos(p * f) + a * math.sin(p * f)
        return out
    assert np.dot(rot(q, 11, True), rot(k, 5, True)) == pytest.approx(
        float(np.dot(by_hand(q, 11), by_hand(k, 5))), rel=1e-4)


# --------------------------------------------- (d) routing, no token dropped


def _loop_router(logits, r):
    """Group-limited top-k, written as loops over tokens."""
    idx, weights = [], []
    for row in np.asarray(logits, np.float64):
        e = np.exp(row - row.max())
        s = e / e.sum()
        per = r.experts // r.groups
        group_best = [s[g * per:(g + 1) * per].max()
                      for g in range(r.groups)]
        kept = sorted(range(r.groups), key=lambda g: -group_best[g])[
            :r.top_groups]
        allowed = [i for g in kept for i in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda i: -s[i])[:r.top_k]
        w = np.asarray([s[i] for i in chosen])
        if r.renormalise:
            w = w / w.sum()
        idx.append(chosen)
        weights.append(w * r.scale)
    return np.asarray(idx), np.asarray(weights)


@pytest.mark.parametrize("renormalise", [False, True])
def test_group_limited_routing_matches_a_loop_written_router(renormalise):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    r = moe.Router(experts=16, top_k=3, groups=4, top_groups=2,
                   renormalise=renormalise, scale=16.0)
    logits = np.random.default_rng(0).normal(size=(50, 16)) * 2
    idx, w = moe.route(jnp.asarray(logits, jnp.float32), r)
    want_idx, want_w = _loop_router(logits, r)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    # Never an expert of a group that was not kept.
    assert all(len({i // 4 for i in row}) <= 2 for row in np.asarray(idx))


def _experts(rng, n, d, m):
    return {"w_gate": rng.normal(size=(n, d, m)).astype(np.float32) / 4,
            "w_up": rng.normal(size=(n, d, m)).astype(np.float32) / 4,
            "w_down": rng.normal(size=(n, m, d)).astype(np.float32) / 4}


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _dense_experts(x, idx, w, ex, first=0):
    """Every (token, expert) pair whose expert is in ``ex``, one by one."""
    out = np.zeros_like(x, dtype=np.float64)
    n = ex["w_gate"].shape[0]
    for t in range(x.shape[0]):
        for e, wt in zip(idx[t], w[t]):
            if first <= e < first + n:
                j = e - first
                h = _silu(x[t] @ ex["w_gate"][j]) * (x[t] @ ex["w_up"][j])
                out[t] += wt * (h @ ex["w_down"][j])
    return out


def test_no_token_is_dropped_when_one_expert_takes_every_token():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    rng = np.random.default_rng(1)
    t, d, m = 96, 16, 8
    x = rng.normal(size=(t, d)).astype(np.float32)
    ex = _experts(rng, 8, d, m)
    logits = rng.normal(size=(t, 16)).astype(np.float32)
    logits[:, 3] += 20.0                   # expert 3 leads for every token
    r = moe.Router(experts=16, top_k=3, groups=4, top_groups=2, scale=2.0)
    idx, w = moe.route(jnp.asarray(logits), r)
    with jax.default_matmul_precision("highest"):
        y, sizes = moe.held_experts_ffn(
            jnp.asarray(x), idx, w, jax.tree.map(jnp.asarray, ex), (0, 8))
    assert int(sizes[3]) == t              # all 96, where a capacity of
    #                                        1.25 * 3 * 96 / 16 = 22 drops 74
    assert int(sizes.sum()) == int(((np.asarray(idx) < 8)).sum())
    np.testing.assert_allclose(
        np.asarray(y), _dense_experts(x, np.asarray(idx), np.asarray(w), ex),
        atol=1e-4)
    # ``keep`` leaves a token's pairs out and its row zero.
    keep = np.arange(t) % 2 == 0
    y2, sizes2 = moe.held_experts_ffn(
        jnp.asarray(x), idx, w, jax.tree.map(jnp.asarray, ex), (0, 8),
        keep=jnp.asarray(keep))
    assert int(sizes2[3]) == t // 2
    assert not np.asarray(y2)[~keep].any()


# ------------------------------------------------ (e) the shares add up


def test_the_four_shares_and_the_shared_experts_once_give_the_uncut_layer():
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import deepseek_v2_ref
    from ray_tpu.models import deepseek, deepseek_decode as dd

    whole_cfg = dataclasses.replace(deepseek.PRESETS["debug"],
                                    experts_held=None, n_dense_layers=0,
                                    n_layers=1)
    whole = deepseek.init_params(whole_cfg, jax.random.key(3))
    layer = jax.tree.map(lambda a: a[0], whole["moe"])
    rng = np.random.default_rng(2)
    x0 = jnp.asarray(rng.normal(size=(40, whole_cfg.dim)), jnp.float32)
    # The reference's layer is attention, then experts: what its
    # attention gives is the input the expert layers are compared on.
    with jax.default_matmul_precision("highest"):
        x = deepseek_v2_ref._attention(whole["moe"], 0, x0, whole_cfg,
                                       None)[None]
    keep = jnp.ones((1, 40), bool)

    def routed_and_shared(cfg, experts):
        """``_moe_ffn`` less its residual, and the shared part alone."""
        lay = {**layer, "experts": jax.tree.map(lambda a: a[None], experts),
               "expert_layer": jnp.asarray(0, jnp.int32)}
        with jax.default_matmul_precision("highest"):
            out, sizes = dd._moe_ffn(lay, x, cfg, keep)
            h = dd.rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            shared = dd._swiglu(layer["shared"], h)
        return out - x, shared, sizes

    uncut, shared, sizes_all = routed_and_shared(whole_cfg, layer["experts"])
    parts, pairs = [], 0
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(whole_cfg, experts_held=(first, 4))
        mine = jax.tree.map(lambda a: a[first:first + 4], layer["experts"])
        part, _, sizes = routed_and_shared(cfg, mine)
        parts.append(part - shared)        # this chip's routed part
        pairs += int(sizes.sum())
    assert pairs == int(sizes_all.sum()) == 40 * whole_cfg.top_k
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(uncut), atol=1e-4)
    # ... and the uncut layer is the reference's, which has its own router.
    ref = deepseek_v2_ref._moe_layer(whole["moe"], 0, x0, whole_cfg, None)
    np.testing.assert_allclose(np.asarray(ref - x[0]),
                               np.asarray(uncut[0]), atol=1e-4)


def test_weights_are_made_leaf_by_leaf_in_the_compute_dtype():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek, deepseek_decode as dd

    cfg = dataclasses.replace(deepseek.PRESETS["debug"],
                              dtype=jnp.bfloat16)
    a = deepseek.init_params(cfg, jax.random.key(1))
    b = deepseek.init_params(cfg, jax.random.key(1))
    leaves = jax.tree_util.tree_leaves_with_path(a)
    assert sum(x.size for _, x in leaves) == deepseek.param_count(cfg)
    for (path, x), y in zip(leaves, jax.tree.leaves(b)):
        name = str(path[-1].key)
        assert x.dtype == (jnp.float32 if name in deepseek.NORM_LEAVES
                           else jnp.bfloat16), name
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    # Already in the compute dtype: the engine's tree is the same arrays.
    held = dd.compute_weights(a, cfg)
    assert all(x is y for x, y in zip(jax.tree.leaves(held),
                                      jax.tree.leaves(a)))


# ------------------------------------- the prefill kernel, the decode view


@pytest.mark.parametrize("offsets", [(0, 0), (0, 37), (96, 5)])
def test_latent_prefill_kernel_matches_plain_attention(offsets,
                                                       monkeypatch):
    """Traced offsets, several query and key tiles, tiles above the causal
    frontier (whose keys are poisoned: a tile that should be skipped and
    is not shows)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention
    from ray_tpu.ops.latent_attention import latent_prefill_attention

    monkeypatch.setattr(latent_attention, "BLOCK_Q", 16)
    monkeypatch.setattr(latent_attention, "BLOCK_K", 32)
    rng = np.random.default_rng(0)
    B, H, S, C, dn, dr, dv = 2, 3, 32, 128, 16, 8, 16
    qn, qp = rng.normal(size=(B, H, S, dn)), rng.normal(size=(B, H, S, dr))
    kn, kp = rng.normal(size=(B, H, C, dn)), rng.normal(size=(B, C, dr))
    v = rng.normal(size=(B, H, C, dv))
    off = np.asarray(offsets, np.int32)
    for b in range(B):                      # poison what no query may see
        kn[b, :, off[b] + S:] = 1e4
        v[b, :, off[b] + S:] = 1e4
        dead = -(-(off[b] + S) // 32) * 32  # tiles wholly past the frontier
        v[b, :, dead:] = np.nan
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: latent_prefill_attention(*a, 0.3))(
                f32(qn), f32(qp), f32(kn), f32(kp), f32(v), jnp.asarray(off))
    s = (np.einsum("bhsd,bhkd->bhsk", qn, kn)
         + np.einsum("bhsd,bkd->bhsk", qp, kp)) * 0.3
    seen = (np.arange(C)[None, None, :]
            <= (off[:, None] + np.arange(S)[None, :])[:, :, None])
    s = np.where(seen[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhsk,bhkd->bhsd", p, np.nan_to_num(v))
    assert np.isnan(v).any() or max(offsets) + S > C - 32
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)


def test_the_decode_view_lists_a_slots_pages_in_whole_groups():
    from ray_tpu.models import deepseek_decode as dd

    G = dd.VIEW_GROUP
    tables = np.arange(1, 1 + 4 * 40, dtype=np.int32).reshape(4, 40)
    counts = np.asarray([17, 0, 16, 3])
    assert dd.view_rows(counts) == 32 + 0 + 16 + 16
    view = dd.live_page_view(tables, counts, 128)
    pages, owner, index = view
    # Every aligned group has one owner (-1: nobody).
    groups = owner.reshape(-1, G)
    assert (groups == groups[:, :1]).all()
    assert list(groups[:, 0]) == [0, 0, 2, 3, -1, -1, -1, -1]
    for slot in (0, 2, 3):
        mine = owner == slot
        real = mine & (index < counts[slot])
        assert list(pages[real]) == list(tables[slot, :counts[slot]])
        # A row that pads a group: the scratch page, at an index past the
        # slot's pages, which the position mask hides.
        assert not pages[mine & ~real].any()
        assert (index[mine & ~real] >= counts[slot]).all()
    assert not pages[owner < 0].any()
    with pytest.raises(ValueError, match="do not fit"):
        dd.live_page_view(tables, counts, 48)


def test_rows_of_no_group_are_selected_away_not_multiplied_by_zero(
        monkeypatch):
    """The chip's ragged matmul leaves the rows past the last group (the
    absent experts' pairs) undefined; with NaN there the layer's output
    must still be the held experts' part."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    real = jax.lax.ragged_dot

    def poisoned(x, w, sizes, **kw):
        out = real(x, w, sizes, **kw)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    rng = np.random.default_rng(3)
    t, d, m = 24, 16, 8
    x = rng.normal(size=(t, d)).astype(np.float32)
    ex = _experts(rng, 4, d, m)
    r = moe.Router(experts=16, top_k=3, groups=4, top_groups=2)
    idx, w = moe.route(jnp.asarray(rng.normal(size=(t, 16)), jnp.float32), r)
    with jax.default_matmul_precision("highest"):
        y, sizes = moe.held_experts_ffn(
            jnp.asarray(x), idx, w, jax.tree.map(jnp.asarray, ex), (4, 4))
    assert 0 < int(sizes.sum()) < t * 3      # some pairs held, some absent
    np.testing.assert_allclose(
        np.asarray(y),
        _dense_experts(x, np.asarray(idx), np.asarray(w), ex, first=4),
        atol=1e-4)


# ------------------------ the reference's blocks, the control, the limits


@pytest.mark.parametrize("query_block,head_block", [(8, 2), (16, 4), (5, 1)])
def test_the_references_blocks_change_nothing(model, monkeypatch,
                                              query_block, head_block):
    """Attention by blocks of heads and of queries (what lets the
    reference run 8k tokens beside the served model) against one block of
    everything."""
    import jax

    from benchmarks.reference import deepseek_v2_ref as ref

    cfg, params = model
    tokens = np.asarray(_prompt(np.random.default_rng(5), 40), np.int32)
    jax.clear_caches()
    monkeypatch.setattr(ref, "QUERY_BLOCK", 40)
    monkeypatch.setattr(ref, "HEAD_BLOCK", cfg.n_heads)
    whole = np.asarray(ref.logits(params, tokens, cfg))
    jax.clear_caches()
    monkeypatch.setattr(ref, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(ref, "HEAD_BLOCK", head_block)
    blocked = np.asarray(ref.logits(params, tokens, cfg))
    jax.clear_caches()
    np.testing.assert_allclose(blocked, whole, atol=2e-5)
    rows = np.asarray(ref.logits(params, tokens, cfg, rows=[3, 39]))
    np.testing.assert_allclose(rows, blocked[[3, 39]], atol=1e-6)


def test_the_control_answers_every_cut_and_coarser_weights_leave_the_choice(
        model):
    """``cut_prompt_margins``: one token after each of a prompt's last
    ``n`` cuts; at 16 bits the rounded reference chooses what the
    unrounded one does, at 3 bits it does not."""
    from benchmarks.reference import deepseek_v2_ref as ref

    cfg, params = model
    rng = np.random.default_rng(6)
    prompts = [_prompt(rng, n) for n in (30, 41)]
    fine = ref.cut_prompt_margins(params, cfg, prompts, 12, 16)
    coarse = ref.cut_prompt_margins(params, cfg, prompts, 12, 3)
    assert len(fine) == len(coarse) == 24
    assert max(fine) < 1e-3 and min(coarse) >= 0.0
    assert sum(m > 0.01 for m in coarse) >= 6
    # A cut's token is the rounded reference's argmax at that position.
    said = np.asarray(ref.logits(params, np.asarray(prompts[0], np.int32),
                                 cfg, 3, rows=[29])).argmax(-1)[0]
    lg = np.asarray(ref.logits(params, np.asarray(prompts[0], np.int32),
                               cfg, rows=[29]))[0]
    assert coarse[11] == pytest.approx(float(lg.max() - lg[said]), abs=1e-6)


def test_correct_holds_the_largest_and_the_ranked_margin(capsys):
    """The family hands the harness each reading as a share of its limit
    (``tolerance`` 1): flips below ``RANK`` in number move only the
    largest; a shift of many tokens moves the ranked one."""
    from benchmarks.families import deepseek_v2 as fam

    quiet = [0.0] * 250
    flips = [0.8, 0.5, 0.3] + [fam.RANKED_LIMIT / 4] * 3 + quiet
    assert max(fam.shares_of_limits(flips)) <= fam.Serve.tolerance
    shifted = [fam.RANKED_LIMIT * 2] * fam.RANK + quiet
    assert max(fam.shares_of_limits(shifted)) > fam.Serve.tolerance
    wrong = [fam.LARGEST_LIMIT * 1.5] + quiet
    assert max(fam.shares_of_limits(wrong)) > fam.Serve.tolerance
    assert fam.readings([0.3, 0.1]) == (0.3, 0.1)
    assert "largest 0.8000" in capsys.readouterr().out
