"""Paged KV cache: allocator + paged prefix index units, the paged
programs against the plain reference at the model and engine layers
(``tests/stream_reference.py`` states the tolerance), page-granular
refcount/evict under the PR 3 cancel/deadline paths, overcommitted-pool
concurrency (the >= 1.5x acceptance bar), recompute preemption, and the
chunked-prefill no-starvation invariant (dispatch-order based — the 1-core
CPU rig makes wall-clock invariants meaningless). All CPU, tiny
configs — tier-1 safe."""

import time

import numpy as np
import pytest

from ray_tpu.serve.paging import (PageAllocator, PagedPrefixIndex,
                                  prefix_hash)
from stream_reference import (assert_logits_close,
                              assert_stream_is_the_references)


def _tiny(max_seq_len=256):
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=61, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, mlp_dim=64,
                            max_seq_len=max_seq_len)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _drive(eng, reqs, budget=400):
    for _ in range(budget):
        if all(r.done.is_set() for r in reqs):
            return
        eng.step()
    raise AssertionError(
        f"requests not done in {budget} steps: "
        f"{[r.status for r in reqs]}")


def _solo(params, cfg, prompt, n):
    from ray_tpu.models import llama_decode

    return list(np.asarray(llama_decode.generate(
        params, np.array([prompt], np.int32), cfg, max_new_tokens=n))[0])


# ---------------------------------------------------------- allocator


def test_allocator_alloc_free_incref():
    pa = PageAllocator(4)
    a = pa.alloc(3)
    assert len(a) == 3 and pa.free_count == 1 and pa.in_use == 3
    assert pa.alloc(2) is None          # all-or-nothing
    assert pa.free_count == 1           # failed alloc grants nothing
    pa.incref(a[0])
    pa.free(a)                          # drops to refcount 1 on a[0]
    assert pa.free_count == 3 and pa.refcount(a[0]) == 1
    pa.free([a[0]])
    assert pa.free_count == 4 and pa.in_use == 0
    assert sorted(pa.alloc(4)) == [1, 2, 3, 4]  # id 0 = scratch, reserved


def test_allocator_recycles_lifo():
    pa = PageAllocator(4)
    a = pa.alloc(2)
    pa.free([a[-1]])
    assert pa.alloc(1) == [a[-1]]  # most-recently-freed first


# -------------------------------------------------------- prefix index


def test_index_page_aligned_match_and_dedup():
    pa = PageAllocator(16)
    idx = PagedPrefixIndex(pa, page_tokens=4, max_pages=8, min_tokens=4)
    toks = list(range(10, 29))          # 19 tokens
    pages = pa.alloc(5)
    # Insert grid = largest pow2 <= 19 = 16 tokens = 4 pages.
    assert idx.insert(toks, pages) == 4
    assert idx.insert(toks, pa.alloc(5)) == 0   # dedup on the token key
    m = idx.match(toks)
    assert m is not None
    got, mlen = m
    assert mlen == 16 and got == pages[:4]      # page-aligned, in order
    for p in got:
        assert pa.refcount(p) >= 3  # slot + index pin + match incref
    pa.free(got)                    # the borrower's release
    # Shorter shared prefix matches at ITS page boundary.
    m2 = idx.match(toks[:9] + [99] * 6)
    assert m2 is not None and m2[1] == 8
    pa.free(m2[0])
    # Nested prefixes share one chain: a strict prefix of a cached prompt
    # adds nothing, a longer prompt only the pages past the chain's end.
    assert idx.insert(toks[:8], pages[:2]) == 0
    longer = toks[:16] + list(range(200, 220))  # 36 tokens: grid 32
    assert idx.insert(longer, pages[:4] + pa.alloc(5)) == 4
    assert len(idx) == 8
    m3 = idx.match(longer)
    assert m3 is not None and m3[1] == 32 and m3[0][:4] == pages[:4]
    pa.free(m3[0])


def test_index_min_tokens_and_one_suffix_token():
    pa = PageAllocator(8)
    idx = PagedPrefixIndex(pa, page_tokens=4, max_pages=8, min_tokens=8)
    toks = list(range(16))
    idx.insert(toks, pa.alloc(4))
    assert idx.match(toks[:8]) is None      # match capped at len-1 -> 4
    m = idx.match(toks)  # identical prompt: 16 -> capped at 15 -> 12
    assert m is not None and m[1] == 12
    pa.free(m[0])
    assert idx.match(toks[:5] + [99] * 8) is None  # 4 < min_tokens


def test_index_tail_eviction_shrinks_chain():
    """Eviction unpins page-granular TAIL segments: the LRU leaf goes
    first, and the shortened chain still matches at its new length."""
    pa = PageAllocator(16)
    idx = PagedPrefixIndex(pa, page_tokens=4, max_pages=16, min_tokens=4)
    a_tokens = list(range(16))
    b_tokens = list(range(30, 46))
    a_pages = pa.alloc(4)
    b_pages = pa.alloc(4)
    idx.insert(a_tokens, a_pages)
    m = idx.match(b_tokens[:1] + b_tokens[1:])  # miss; just a query
    assert m is None
    idx.insert(b_tokens, b_pages)               # b is now most recent
    pa.free(a_pages)
    pa.free(b_pages)                            # only index pins remain
    assert idx.reclaim(1) == 1                  # evicts a's deepest leaf
    assert pa.free_count == 16 - 7
    m = idx.match(a_tokens + [99])
    assert m is not None and m[1] == 12         # chain shrank 16 -> 12
    pa.free(m[0])
    # b untouched.
    m = idx.match(b_tokens + [99])
    assert m is not None and m[1] == 16
    pa.free(m[0])


def test_index_reclaim_skips_borrowed_pages():
    """Allocation-pressure reclaim only evicts entries whose page it
    holds the LAST reference to — unpinning a page a live slot still
    borrows frees nothing."""
    pa = PageAllocator(8)
    idx = PagedPrefixIndex(pa, page_tokens=4, max_pages=8, min_tokens=4)
    toks = list(range(8))
    pages = pa.alloc(2)
    idx.insert(toks, pages)     # refcount 2 on both (slot + pin)
    assert idx.reclaim(2) == 0  # slot still borrows: nothing freed
    pa.free(pages)              # slot done
    assert idx.reclaim(2) == 2
    assert pa.free_count == 8


def test_index_hashes_on_pow2_grid():
    pa = PageAllocator(16)
    idx = PagedPrefixIndex(pa, page_tokens=4, max_pages=16, min_tokens=4)
    toks = np.arange(100, 116, dtype=np.int32)
    idx.insert(toks, pa.alloc(4))
    # Chain entries at 4/8/12/16 tokens; advertised = pow2 lengths only.
    assert sorted(idx.hashes()) == sorted(
        [prefix_hash(toks[:4]), prefix_hash(toks[:8]),
         prefix_hash(toks[:16])])


# ------------------------------------------- model-level bit-exactness


def test_paged_matches_contiguous_across_boundaries():
    """Paged prefill logits are BIT-EXACT vs the reference's contiguous
    cache (``ld.prefill``, same capacity): the attention is the same
    program, only the K/V destination differs. The paged decode step's
    are the reference ``ld.decode_step``'s within ``LOGITS_ATOL`` while
    the sequence crosses page and bucket boundaries: the same softmax,
    summed page by page over the list of the row's live pages."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    cfg, params = _tiny()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    cap, T = 64, 8
    cont = ld.init_cache(cfg, 1, cap)
    lc, cont = ld.prefill(params, jnp.asarray(prompt[None]), cont, cfg)
    pool = ld.init_page_pool(cfg, 8, T)
    bt = np.zeros((1, cap // T), np.int32)
    bt[0, :] = range(1, 9)  # pre-plumb the whole row: growth is host-side
    lp, pool = ld.paged_prefill(params, jnp.asarray(prompt[None]), pool,
                                jnp.asarray(bt[:, :2]), cfg)
    assert jnp.array_equal(lc, lp), "prefill logits diverged"
    lens = jnp.asarray([13], jnp.int32)
    ta = jnp.argmax(lc, -1).astype(jnp.int32)
    tb = jnp.argmax(lp, -1).astype(jnp.int32)
    # 13 -> 33 tokens: crosses page boundaries at 16, 24, 32.
    for i in range(20):
        assert int(ta[0]) == int(tb[0]), f"token diverged at step {i}"
        la, cont = ld.decode_step(params, cont, ta, cfg)
        view = ld.live_page_view(bt, [-(-(14 + i) // T)], 8)
        lb, pool, lens = ld.paged_decode_step(
            params, pool, jnp.asarray(view), lens, tb, cfg)
        assert_logits_close(lb, la)
        ta = jnp.argmax(la, -1).astype(jnp.int32)
        tb = jnp.argmax(lb, -1).astype(jnp.int32)


def test_paged_suffix_prefill_token_exact():
    """Chunked continuation: prefill a prompt in two paged suffix calls
    and decode — token stream identical to the solo contiguous path."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    cfg, params = _tiny()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    T = 8
    pool = ld.init_page_pool(cfg, 8, T)
    bt = np.zeros((1, 8), np.int32)
    bt[0, :4] = [1, 2, 3, 4]
    _, pool = ld.paged_prefill(params, jnp.asarray(prompt[None, :16]),
                               pool, jnp.asarray(bt[:, :2]), cfg)
    logits, pool = ld.paged_prefill_suffix(
        params, jnp.asarray(prompt[None, 16:]), pool,
        jnp.asarray(bt[:, :3]), cfg, jnp.asarray([16], np.int32),
        jnp.asarray([24], np.int32))
    toks = [int(jnp.argmax(logits, -1)[0])]
    lens = jnp.asarray([24], jnp.int32)
    t = jnp.argmax(logits, -1).astype(jnp.int32)
    view = jnp.asarray(ld.live_page_view(bt, [4], 4))
    for _ in range(5):
        logits, pool, lens = ld.paged_decode_step(
            params, pool, view, lens, t, cfg)
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(t[0]))
    assert toks == _solo(params, cfg, prompt.tolist(), 6)


# ------------------------------------------- the pool is written in place
#
# The forwards that carry the pool through their layer loop, at one small
# geometry: three rows over pages of four tokens, a pool of twelve pages
# of which 4, 11 and 12 belong to no row. Each call writes three positions
# a row (one a row for the single decode step); row 2's run past its page
# window, where the scratch-page rule takes over.

IN_PLACE = ("paged_decode_step", "paged_prefill_suffix")
_BT = np.array([[1, 2, 3, 0], [5, 6, 0, 0], [7, 8, 9, 10]], np.int32)
_LENS = np.array([9, 5, 14], np.int32)
_PAGES, _T = 12, 4


def _in_place_call(name, cfg):
    """``(fn(params, pool) -> (.., pool, ..), positions written a row)``
    for forward ``name``; the pool is argument 1, as the engine's programs
    take it, so ``donate_argnums=(1,)`` is the engine's donation."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    bt, lens = jnp.asarray(_BT), jnp.asarray(_LENS)
    view = jnp.asarray(ld.live_page_view(_BT, (_BT > 0).sum(1), 16))
    rows = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (3, 3)).astype(np.int32)
    if name == "paged_decode_step":
        return (lambda params, pool: ld.paged_decode_step(
            params, pool, view, lens, jnp.asarray(rows[:, 0]), cfg)), 1
    return (lambda params, pool: ld.paged_prefill_suffix(
        params, jnp.asarray(rows), pool, bt, cfg, lens, lens + 3)), 3


def _random_pool(cfg, dtype=None):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    zeros = ld.init_page_pool(cfg, _PAGES, _T, dtype=dtype)
    kk, kv = jax.random.split(jax.random.key(7))
    return {"k": jax.random.normal(kk, zeros["k"].shape, zeros["k"].dtype),
            "v": jax.random.normal(kv, zeros["v"].shape, zeros["v"].dtype)}


@pytest.mark.parametrize("name", IN_PLACE)
def test_pool_is_not_copied_to_be_written(name):
    """The mechanism's guard: jitted with the pool donated, the COMPILED
    program of each forward holds no ``copy`` and no
    ``dynamic-update-slice`` whose result has the pool's element count.
    With the pool as a scanned input and a stacked output of the layer
    loop there were two of each: every call copied the whole pool to
    store a few rows of it. This is the CPU compiler's answer at a debug
    geometry; the proof is the chip's: no pool-sized operation in the
    ``breakdown.device_ops`` of a traced benchmark run (PERF.md)."""
    import re

    import jax
    import jax.numpy as jnp

    cfg, params = _tiny()
    # float32: the CPU backend scatters bfloat16 through a float32 copy
    # of the operand, which says nothing about the loop.
    pool = _random_pool(cfg, jnp.float32)
    fn, _ = _in_place_call(name, cfg)
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool).compile().as_text()
    assert "while" in text                    # the layer loop is there
    size = int(np.prod(pool["k"].shape))
    moved = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* (copy|dynamic-update-slice)\(",
                      line)
        if m and np.prod([int(d) for d in m[1].split(",")]) == size:
            moved.append(line.strip()[:120])
    assert not moved, moved


@pytest.mark.parametrize("name", IN_PLACE)
def test_in_place_write_touches_only_its_positions(name):
    """From a pool of random values, one call changes the positions it
    writes (in every layer: a write at the wrong layer index would leave
    one untouched) and the scratch page 0, and nothing else: pages no
    block table maps and every mapped position below the rows' lengths
    are the values they were. On a two-device mesh the same holds and
    the returned pool keeps ``decode_shardings``' placement with nothing
    pinning it: the flat view inside the program shards like the pool."""
    import jax

    from ray_tpu.models import llama_decode as ld
    from ray_tpu.parallel.mesh import decode_mesh
    from ray_tpu.parallel.sharding import axis_rules

    cfg, params = _tiny()
    fn, n_written = _in_place_call(name, cfg)
    # One pool per call (the same values each time): a call donates its own.
    before = {n: np.asarray(a, np.float32)
              for n, a in _random_pool(cfg).items()}
    written = np.zeros((_PAGES + 1, _T), bool)
    for b, start in enumerate(_LENS):
        for p in range(start, start + n_written):
            if p < _BT.shape[1] * _T:
                written[_BT[b, p // _T], p % _T] = True
    assert not written[[0, 4, 11, 12]].any()
    assert written.sum() == (3 if n_written == 1 else 3 + 3 + 2)
    pages, offs = written.nonzero()
    untouched = ~written
    untouched[0] = False                       # the scratch page may change

    def check(out_pool):
        for n in ("k", "v"):
            after = np.asarray(out_pool[n], np.float32)
            assert after.shape == before[n].shape
            np.testing.assert_array_equal(after[:, untouched],
                                          before[n][:, untouched])
            changed = (after != before[n]).any(axis=(-1, -2))  # (L, P, T)
            assert changed[:, pages, offs].all()

    out = jax.jit(fn, donate_argnums=(1,))(params, _random_pool(cfg))
    check(out[1])

    mesh = decode_mesh((1, 2))
    sparams, sh = ld.shard_decode_state(params, cfg, mesh)
    assert sh["rules"]["kv_heads"] == "model"  # the pool really is split
    pool_sh = {n: sh["pool"][n] for n in ("k", "v")}
    with axis_rules(mesh, sh["rules"]):
        out = jax.jit(fn, donate_argnums=(1,))(
            sparams, jax.device_put(_random_pool(cfg), pool_sh))
    check(out[1])
    for n in ("k", "v"):
        assert out[1][n].sharding.is_equivalent_to(pool_sh[n], 5), \
            out[1][n].sharding


# ------------------------------------- the decode step's live-page view
#
# ``paged_decode_step`` reads a flat list of the pages its slots hold
# (``live_page_view``), ``N`` rows on a ladder, and takes each slot's
# softmax across its rows. Pages of four tokens; four slots of 32 pages
# make the ladder (64, 128), as the engine derives it.

_LP_T, _LP_CAP, _LP_LADDER = 4, 128, (64, 128)


def _lp_prompts(cfg, lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def _lp_rows(prompts):
    rows = np.zeros((len(prompts), max(map(len, prompts))), np.int32)
    for b, p in enumerate(prompts):
        rows[b, :len(p)] = p
    return rows, np.array([len(p) for p in prompts], np.int32)


def _lp_reference(cfg, params, prompts):
    """The reference's contiguous cache filled with ``prompts``:
    ``(last logits, cache)``."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    rows, lens = _lp_rows(prompts)
    return ld.prefill(params, jnp.asarray(rows),
                      ld.init_cache(cfg, len(prompts), _LP_CAP), cfg,
                      lengths=jnp.asarray(lens))


def _lp_view(tables, lens, ahead, stepping=None):
    """The view a step that writes ``ahead`` tokens a slot needs, on the
    smallest rung of the ladder: ``(view, rung)``."""
    from ray_tpu.models import llama_decode as ld

    counts = -(-(np.asarray(lens) + ahead) // _LP_T)
    if stepping is not None:
        counts = np.where(stepping, counts, 0)
    rung = next(n for n in _LP_LADDER if n >= counts.sum())
    return ld.live_page_view(tables, counts, rung), rung


def _lp_lockstep(cfg, params, prompts, pool, tables, steps):
    """Teacher-forced on the reference's greedy tokens, the paged step's
    logits are the reference's at every step; returns the rungs used and
    the pool."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    logits, cont = _lp_reference(cfg, params, prompts)
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    rungs = set()
    for _ in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        view, rung = _lp_view(tables, np.asarray(lens), 1)
        rungs.add(rung)
        logits, cont = ld.decode_step(params, cont, tok, cfg)
        got, pool, lens = ld.paged_decode_step(
            params, pool, jnp.asarray(view), lens, tok, cfg)
        assert_logits_close(got, logits)
    return rungs, pool


def _lp_own_pages(slots, pages_each=_LP_CAP // _LP_T):
    """Block tables in which every slot owns its whole window."""
    return np.arange(1, 1 + slots * pages_each, dtype=np.int32).reshape(
        slots, pages_each)


def _lp_ragged_two_rungs(cfg, params):
    """Contexts of 3 to 118 tokens fill 64 pages, then 65: the lowest
    rung and the one above it, one set of logits."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    prompts = _lp_prompts(cfg, (3, 39, 90, 118))
    tables = _lp_own_pages(4)
    rows, lens = _lp_rows(prompts)
    _, pool = ld.paged_prefill(
        params, jnp.asarray(rows), ld.init_page_pool(cfg, 128, _LP_T),
        jnp.asarray(tables), cfg, lengths=jnp.asarray(lens))
    rungs, _ = _lp_lockstep(cfg, params, prompts, pool, tables, 6)
    assert rungs == {64, 128}


def _lp_shared_page(cfg, params):
    """Two slots borrow pages 1 and 2 (a prefix hit): a row of the view
    each, read by both, written by neither."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    head, = _lp_prompts(cfg, (8,))
    tails = _lp_prompts(cfg, (3, 5), seed=12)
    prompts = [np.concatenate([head, t]) for t in tails]
    tables = np.zeros((2, _LP_CAP // _LP_T), np.int32)
    tables[0, :5] = [1, 2, 3, 6, 7]
    tables[1, :6] = [1, 2, 4, 5, 8, 9]
    pool = ld.init_page_pool(cfg, 9, _LP_T)
    _, pool = ld.paged_prefill(params, jnp.asarray(prompts[0][None]), pool,
                               jnp.asarray(tables[:1, :3]), cfg)
    _, pool = ld.paged_prefill_suffix(
        params, jnp.asarray(tails[1][None]), pool,
        jnp.asarray(tables[1:, :4]), cfg, jnp.asarray([8], jnp.int32),
        jnp.asarray([13], jnp.int32))
    view, _ = _lp_view(tables, [11, 13], 1)
    assert sorted(view[0][view[1] >= 0].tolist()) == [1, 1, 2, 2, 3, 4, 5]
    before = np.asarray(pool["k"][:, 1:3], np.float32)
    _, pool = _lp_lockstep(cfg, params, prompts, pool, tables, 7)
    np.testing.assert_array_equal(
        np.asarray(pool["k"][:, 1:3], np.float32), before)


def _lp_idle_and_prefilling(cfg, params):
    """Slots 0 and 2 decode beside an idle slot and one mid-prefill,
    whose cursor is parked INSIDE its second page: neither owns a row,
    both get finite logits, both write to the scratch page, and the
    mid-prefill slot's pages are the values they were."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    prompts = _lp_prompts(cfg, (6, 13))
    tables = np.zeros((4, _LP_CAP // _LP_T), np.int32)
    tables[0, :2] = [1, 2]
    tables[2, :4] = [3, 4, 5, 6]
    tables[3, :2] = [9, 10]
    pool = _random_pool(cfg)
    rows, lens = _lp_rows(prompts)
    _, pool = ld.paged_prefill(
        params, jnp.asarray(rows), pool, jnp.asarray(tables[[0, 2], :4]),
        cfg, lengths=jnp.asarray(lens))
    logits, cont = _lp_reference(cfg, params, prompts)
    tok = np.zeros((4,), np.int32)
    tok[[0, 2]] = np.argmax(logits, -1)
    lens4 = np.array([6, 0, 13, 6], np.int32)
    stepping = np.array([True, False, True, False])
    view, rung = _lp_view(tables, lens4, 1, stepping)
    assert rung == 64 and set(view[1].tolist()) == {-1, 0, 2}
    before = {n: np.asarray(pool[n], np.float32) for n in ("k", "v")}
    want, _ = ld.decode_step(params, cont, jnp.asarray(tok[[0, 2]]), cfg)
    got, pool, lens_out = ld.paged_decode_step(
        params, pool, jnp.asarray(view), jnp.asarray(lens4),
        jnp.asarray(tok), cfg)
    assert np.isfinite(np.asarray(got)).all()
    assert_logits_close(np.asarray(got)[[0, 2]], want)
    assert np.asarray(lens_out).tolist() == [7, 1, 14, 7]
    written = np.zeros((_PAGES + 1, _LP_T), bool)
    written[2, 2] = written[6, 1] = True        # positions 6 and 13
    written[0] = True                           # the scratch page may change
    for n in ("k", "v"):
        after = np.asarray(pool[n], np.float32)
        np.testing.assert_array_equal(after[:, ~written],
                                      before[n][:, ~written])
        assert (after[:, 0] != before[n][:, 0]).any()


def _lp_decode_crosses_page(cfg, params):
    """Four greedy steps, each on the view of the pages its write needs:
    both slots cross a page boundary on the way (8 and 12), and their
    tokens are the reference's."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    prompts = _lp_prompts(cfg, (6, 11))
    tables = _lp_own_pages(2)
    rows, lens = _lp_rows(prompts)
    logits, pool = ld.paged_prefill(
        params, jnp.asarray(rows), ld.init_page_pool(cfg, 64, _LP_T),
        jnp.asarray(tables), cfg, lengths=jnp.asarray(lens))
    lens = jnp.asarray(lens)
    served, held = [], []
    for _ in range(5):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        served.append(np.asarray(tok))
        view, _ = _lp_view(tables, np.asarray(lens), 1)
        held.append([(view[1] == b).sum() for b in (0, 1)])
        logits, pool, lens = ld.paged_decode_step(
            params, pool, jnp.asarray(view), lens, tok, cfg)
    assert held[0] == [2, 3] and held[-1] == [3, 4]
    assert np.asarray(lens).tolist() == [11, 16]
    for b, prompt in enumerate(prompts):
        assert_stream_is_the_references(params, cfg, prompt,
                                        [int(t[b]) for t in served])


def _lp_preempted_returns(cfg, params):
    """An engine on this geometry under page pressure: the youngest
    request is preempted and returns through a prefill of prompt +
    emitted tokens, then decodes on through the view; every stream is
    the reference's, and every step read a rung of the ladder."""
    from ray_tpu.serve.decode import DecodeEngine

    eng = DecodeEngine(params, cfg, slots=4, capacity=_LP_CAP,
                       page_tokens=_LP_T, pool_pages=40,
                       prefix_pool_entries=0)
    assert eng._view_ladder == _LP_LADDER
    prompts = [p.tolist() for p in _lp_prompts(cfg, (30, 30, 30, 30))]
    reqs = [eng.submit(p, max_new_tokens=60) for p in prompts]
    _drive(eng, reqs, budget=3000)
    assert eng.preempted > 0
    for p, r in zip(prompts, reqs):
        assert r.status == "completed" and len(r.output) == 60
        assert_stream_is_the_references(params, cfg, p, r.output)
    widths = {r["view_pages"] for r in eng.timeline()["rows"]
              if "view_pages" in r}
    assert widths and widths <= set(_LP_LADDER)
    assert eng.stats()["pages_in_use"] == 0
    eng.shutdown()


LIVE_PAGE_CASES = {
    "ragged_two_rungs": _lp_ragged_two_rungs,
    "shared_page": _lp_shared_page,
    "idle_and_prefilling": _lp_idle_and_prefilling,
    "decode_crosses_page": _lp_decode_crosses_page,
    "preempted_returns": _lp_preempted_returns,
}


@pytest.mark.parametrize("case", LIVE_PAGE_CASES)
def test_live_page_step_is_the_references(case):
    """The live-page decode step against the reference ``decode_step``
    (``LOGITS_ATOL`` / ``MARGIN`` of ``stream_reference.py``)."""
    cfg, params = _tiny(max_seq_len=512)
    LIVE_PAGE_CASES[case](cfg, params)


def test_decode_at_the_lowest_rung_holds_nothing_capacity_wide():
    """The mechanism's guard, beside the in-place one above: compiled at
    the lowest rung (64 rows, where 4 slots x 32 pages are 128), no
    operation of the decode step has a result of the capacity-wide
    shapes, the gathered view ``[128, T, KV, D]`` (``[B, 32 x T, KV,
    D]`` a slot) or scores ``[B, KV, G, 32 x T]``, and the layer loop holds exactly one K and one V
    gather under ``paged_gather``, each with a 4-d result of the rung's
    rows: what the benchmark's ``paged_attn_roofline_pct`` credits one
    layer's K or V for (``benchmarks/kernel_counts.py``), so a second
    gather a layer would count the useful bytes twice. This is the CPU
    compiler's answer; the chip's is in the traced runs (PERF.md)."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    cfg, params = _tiny(max_seq_len=512)
    slots, width = 4, _LP_CAP // _LP_T
    pool = ld.init_page_pool(cfg, 40, _LP_T, dtype=jnp.float32)
    view = ld.live_page_view(_lp_own_pages(slots), np.full(slots, 5), 64)
    text = jax.jit(
        lambda params, pool: ld.paged_decode_step(
            params, pool, jnp.asarray(view), jnp.full((slots,), 19),
            jnp.zeros((slots,), jnp.int32), cfg),
        donate_argnums=(1,)).lower(params, pool).compile().as_text()
    kv, d = cfg.n_kv_heads, cfg.head_dim
    wide = {(slots * width, _LP_T, kv, d), (slots, _LP_CAP, kv, d),
            (slots, kv, cfg.n_heads // kv, _LP_CAP)}
    gathers = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* ([\w\-]+)\(", line)
        if not m:
            continue
        # Unit axes dropped: the CPU compiler keeps the gather's window
        # axis, ``[64, 1, T, KV, D]``, where the chip's folds it away.
        shape = tuple(int(x) for x in m[1].split(",") if x != "1")
        assert shape not in wide, line.strip()[:160]
        if m[2] == "gather" and "paged_gather" in line:
            gathers.append(shape)
    assert gathers == [(64, _LP_T, kv, d)] * 2, gathers


# ------------------------------------------------ engine bit-exactness


def test_engine_paged_streams_match_solo():
    """The engine emits exactly solo generate's streams for prompt
    lengths straddling prefill-bucket and page boundaries (whole-prompt
    prefills: the paged programs are the reference's, bit for bit)."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (5, 15, 16, 17, 31, 33)]
    eng = DecodeEngine(params, cfg, slots=3, capacity=64, page_tokens=16,
                       prefix_pool_entries=0)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng, reqs)
    eng.shutdown()
    for p, r in zip(prompts, reqs):
        assert r.output == _solo(params, cfg, p, 6)


def test_engine_paged_prefix_hit_zero_copy_and_exact():
    """A prefix hit splices block-table entries (pages_in_use does not
    grow at insert) and the spliced stream stays token-exact."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 32).tolist()
    eng = DecodeEngine(params, cfg, slots=2, capacity=128, page_tokens=16,
                       prefix_pool_entries=8, prefix_match_min_tokens=8)
    r1 = eng.submit(shared + [7, 8], max_new_tokens=2)
    _drive(eng, [r1])
    s = eng.stats()
    # Insert pinned the slot's own pages: nothing new was allocated.
    assert s["pages_pinned"] == 2 and s["pages_in_use"] == 2
    p2 = shared + rng.integers(0, cfg.vocab_size, 3).tolist()
    r2 = eng.submit(p2, max_new_tokens=5)
    _drive(eng, [r2])
    assert r2.prefix_len == 32
    assert r2.output == _solo(params, cfg, p2, 5)
    st = eng.prefix.stats()
    assert st["hits"] == 1 and st["prefill_tokens_saved"] == 32
    eng.shutdown()


def test_a_slot_seated_behind_a_shared_prefix_leaves_it_until_its_chunks():
    """A request seated for a chunked prefill behind a spliced prefix
    waits, un-ticked, while an older prompt takes the one chunk a step:
    its cursor lies INSIDE the first shared page all that time, and the
    decode steps of the other borrower leave the prefix's pages bit for
    bit (a slot outside the view writes to the scratch page). Both
    borrowers then stream the reference's tokens."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(17)
    head = rng.integers(0, cfg.vocab_size, 16).tolist()
    first = head + rng.integers(0, cfg.vocab_size, 3).tolist()
    older = rng.integers(0, cfg.vocab_size, 40).tolist()   # five chunks
    second = head + rng.integers(0, cfg.vocab_size, 12).tolist()
    eng = DecodeEngine(params, cfg, slots=3, capacity=128, page_tokens=4,
                       prefill_chunk_tokens=8, prefix_pool_entries=8,
                       prefix_match_min_tokens=8)
    r1 = eng.submit(first, max_new_tokens=24)
    while not r1.generated:
        eng.step()                  # its own three chunks
    assert eng.stats()["active"] == 1 and eng.stats()["pages_pinned"] >= 4
    r3 = eng.submit(older, max_new_tokens=3)
    r2 = eng.submit(second, max_new_tokens=6)
    eng.step()                      # both seated; the older takes the chunk
    shared = list(r2.prefix_pages)
    assert r2.prefix_len == 16 and shared == eng._slot_pages[r1.slot][:4]
    before = {n: np.asarray(eng.cache[n][:, shared], np.float32)
              for n in ("k", "v")}
    waited = 0
    while r2.prefilled == r2.prefix_len and r2.slot in eng._prefilling:
        assert int(np.asarray(eng.cache["length"])[r2.slot]) < 16
        stepped = eng.step()
        assert stepped >= 1         # the first borrower decodes meanwhile
        waited += 1
        for n in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(eng.cache[n][:, shared], np.float32), before[n])
    assert waited >= 4
    _drive(eng, [r1, r2, r3])
    for n in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(eng.cache[n][:, shared], np.float32), before[n])
    for prompt, req in ((first, r1), (second, r2), (older, r3)):
        assert req.status == "completed"
        assert_stream_is_the_references(params, cfg, prompt, req.output)
    eng.shutdown()


# --------------------------------------- overcommit / refcount / evict


def test_paged_overcommit_sustains_1p5x_concurrency():
    """ISSUE 6 acceptance: with kv_page_tokens=64, the engine sustains
    >= 1.5x more concurrent active requests in the same pool bytes than
    whole-row capacity allows — here 12 active in a pool whose bytes
    hold 6 whole rows (2.0x), every stream exact."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=512)
    slots, capacity, pool_pages, T = 12, 256, 24, 64
    whole_rows = pool_pages * T // capacity
    assert whole_rows == 6
    eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                       page_tokens=T, pool_pages=pool_pages,
                       prefix_pool_entries=0)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, 70).tolist()
               for _ in range(slots)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    active = eng.stats()["active"]
    assert active == slots >= 1.5 * whole_rows
    _drive(eng, reqs)
    assert eng.preempted == 0  # 12 x 2 pages fit exactly: no thrash
    for p, r in zip(prompts, reqs):
        assert r.output == _solo(params, cfg, p, 8)
    assert eng.stats()["pages_in_use"] == 0
    eng.shutdown()


def test_paged_cancel_frees_nonshared_pages_within_one_step():
    """PR 3 cancel path at page granularity: a cancelled active request
    frees every non-shared page at the next step boundary; pages pinned
    by the prefix index survive."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 32).tolist()
    eng = DecodeEngine(params, cfg, slots=2, capacity=128, page_tokens=16,
                       prefix_pool_entries=8, prefix_match_min_tokens=8)
    r1 = eng.submit(shared + [1, 2], max_new_tokens=2)
    _drive(eng, [r1])
    pinned = eng.stats()["pages_pinned"]
    assert pinned == 2
    r2 = eng.submit(shared + [5, 6, 7], max_new_tokens=60)
    eng.step()
    assert eng.stats()["active"] == 1
    assert eng.cancel(r2.request_id)
    eng.step()  # ONE step boundary: slot reaped before decode
    s = eng.stats()
    assert r2.done.is_set() and r2.status == "cancelled"
    assert s["active"] == 0
    assert s["pages_in_use"] == pinned == s["pages_pinned"]
    eng.shutdown()


def test_paged_deadline_mid_chunked_prefill_frees_pages():
    """A deadline firing while a long prompt is mid-chunked-prefill
    retires the slot and frees its pages within one step."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=512)
    rng = np.random.default_rng(6)
    eng = DecodeEngine(params, cfg, slots=2, capacity=256, page_tokens=16,
                       prefix_pool_entries=0, prefill_chunk_tokens=16)
    prompt = rng.integers(0, cfg.vocab_size, 200).tolist()
    req = eng.submit(prompt, max_new_tokens=4, deadline_s=30.0)
    eng.step()  # admitted to a prefilling slot
    eng.step()  # a couple of chunks
    assert eng.stats()["prefilling"] == 1
    assert eng.stats()["pages_in_use"] > 0
    # Force the expiry (white-box): wall-clock deadlines short enough to
    # fire mid-prefill for real lose races to jit compilation on this
    # 1-core rig; the reap path only reads the absolute deadline.
    req.deadline = time.monotonic() - 0.01
    eng.step()  # reap notices the expiry
    s = eng.stats()
    assert req.done.is_set() and req.status == "deadline_exceeded"
    assert s["prefilling"] == 0 and s["pages_in_use"] == 0
    with pytest.raises(Exception):
        req.raise_for_status()
    eng.shutdown()


def test_paged_preemption_recovers_exact_streams():
    """Pool pressure preempts the youngest request (recompute-style
    requeue); every stream still completes as the reference's, within
    the stated margin (a preempted request returns through a prefill of
    prompt + emitted tokens, which rounds unlike the decode steps it
    replaces). 4 slots x (30 + 90) tokens need 32 pages against a
    20-page pool."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=512)
    rng = np.random.default_rng(7)
    eng = DecodeEngine(params, cfg, slots=4, capacity=256, page_tokens=16,
                       pool_pages=20, prefix_pool_entries=0)
    prompts = [rng.integers(0, cfg.vocab_size, 30).tolist()
               for _ in range(4)]
    reqs = [eng.submit(p, max_new_tokens=90) for p in prompts]
    _drive(eng, reqs, budget=3000)
    assert eng.preempted > 0
    assert all(r.status == "completed" for r in reqs)
    for p, r in zip(prompts, reqs):
        assert len(r.output) == 90
        assert_stream_is_the_references(params, cfg, p, r.output)
    assert eng.stats()["pages_in_use"] == 0
    eng.shutdown()


# --------------------------------------------- chunked-prefill fairness


def test_chunked_prefill_never_starves_active_slots():
    """The no-decode-starvation invariant, by the ORDER OF DISPATCHES
    (the order the device runs them in): while a long prompt
    chunk-prefills, a decode lies between any two of its chunks, and
    EVERY active slot emits a token on every step — a 4k-class admission
    can cost active streams at most one chunk between tokens, never its
    whole prefill. A host step may dispatch two chunks, its own and the
    next step's ahead of the fetch (PR 53), never two with no decode
    between them. Un-chunked, the same admission stalls actives for the
    entire monolithic prefill (one step)."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=1024)
    rng = np.random.default_rng(8)
    eng = DecodeEngine(params, cfg, slots=3, capacity=512, page_tokens=32,
                       prefix_pool_entries=0, prefill_chunk_tokens=32)
    actives = [eng.submit(rng.integers(0, cfg.vocab_size, 12).tolist(),
                          max_new_tokens=64) for _ in range(2)]
    eng.step()
    assert eng.stats()["active"] == 2
    long_req = eng.submit(
        rng.integers(0, cfg.vocab_size, 400).tolist(),  # 13 chunks
        max_new_tokens=2)
    order = []
    dispatch = eng._dispatch_fresh

    def spy(key, call, then=None, **attrs):
        order.append(attrs.get("program", key[0]))
        return dispatch(key, call, then, **attrs)

    eng._dispatch_fresh = spy
    while not long_req.done.is_set():
        before = [r.generated for r in actives]
        chunks_before = eng.prefill_chunks
        assert eng.step() >= 2
        # Its own chunk (where none went ahead) and the next step's.
        assert eng.prefill_chunks <= chunks_before + 2
        for b, r in zip(before, actives):
            assert r.generated == b + 1, "active slot starved by a prefill"
    chunks = [i for i, p in enumerate(order) if p == "prefill_chunk"]
    assert len(chunks) >= 13  # the long prompt really was chunked
    for a, b in zip(chunks, chunks[1:]):
        assert "decode" in order[a + 1:b], order[a:b + 1]
    # All but the first went ahead of a fetch.
    assert eng.prefill_chunks_ahead == eng.prefill_chunks - 1
    _drive(eng, actives + [long_req])
    # Interleaving preserved exactness for everyone.
    assert long_req.generated == 2
    eng.shutdown()


def test_chunked_prefill_stream_exact_and_ttft_counted():
    """A prompt prefilled in chunks streams the reference's tokens within
    the stated margin (each chunk is a suffix program behind the pages
    the earlier chunks wrote, which rounds unlike one whole-prompt
    prefill), and its first token is timed."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=512)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, 150).tolist()
    eng = DecodeEngine(params, cfg, slots=2, capacity=256, page_tokens=16,
                       prefix_pool_entries=0, prefill_chunk_tokens=32)
    req = eng.submit(prompt, max_new_tokens=5)
    _drive(eng, [req])
    assert len(req.output) == 5
    assert_stream_is_the_references(params, cfg, prompt, req.output)
    assert req.first_token_at is not None
    assert eng.prefill_chunks >= 5  # 150 tokens / 32-token chunks
    eng.shutdown()


def test_chunked_prefill_matches_whole_prompt_prefill():
    """A prompt prefilled as a paged prefill + a paged suffix (the
    chunked path, split at 64) gives the logits and the K cache of the
    reference's ONE ``ld.prefill`` of the whole prompt, within the
    stated tolerance."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_decode as ld

    cfg, params = _tiny(max_seq_len=512)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 122).astype(np.int32)
    whole = np.zeros((1, 128), np.int32)
    whole[0, :122] = prompt
    lsolo, c2 = ld.prefill(params, jnp.asarray(whole),
                           ld.init_cache(cfg, 1, 128), cfg,
                           lengths=jnp.asarray([122], np.int32))
    sfx = np.zeros((1, 64), np.int32)
    sfx[0, :58] = prompt[64:]
    T = 32
    pool = ld.init_page_pool(cfg, 8, T)
    bt = np.zeros((1, 4), np.int32)
    bt[0] = [1, 2, 3, 4]
    _, pool = ld.paged_prefill(params, jnp.asarray(prompt[None, :64]),
                               pool, jnp.asarray(bt[:, :2]), cfg)
    lp, pool = ld.paged_prefill_suffix(
        params, jnp.asarray(sfx), pool, jnp.asarray(bt), cfg,
        jnp.asarray([64], np.int32), jnp.asarray([122], np.int32))
    assert_logits_close(lp, lsolo)
    gathered = np.concatenate(
        [np.asarray(pool["k"][:, bt[0, i]]) for i in range(4)],
        axis=1)[:, :122]
    assert_logits_close(gathered, np.asarray(c2["k"])[:, 0, :122])


def test_paged_soak_invariants():
    """Randomized mixed workload (prefix-sharing, chunked long prompts,
    short fillers, mid-flight cancels, overcommitted pool): every
    request reaches a terminal state, unchunked un-shared completions
    are token-exact vs solo, and the pool drains to exactly the prefix
    pins — no leaked pages, no backlog drift. This soak caught two real
    bugs pre-merge (dataclass __eq__ on numpy tokens crashing requeue
    removal; zero-copy insert running after an instant _finish freed
    the pages)."""
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny(max_seq_len=1024)
    rng = np.random.default_rng(42)
    eng = DecodeEngine(params, cfg, slots=4, capacity=512, page_tokens=32,
                       pool_pages=40,  # overcommitted (4 slots x 16)
                       prefix_pool_entries=8, prefix_match_min_tokens=16,
                       prefill_chunk_tokens=64)
    shared = rng.integers(0, cfg.vocab_size, 128).tolist()
    live, done, submitted = [], [], 0
    for _ in range(400):
        if submitted < 24 and rng.random() < 0.25 and len(live) < 8:
            kind = rng.random()
            if kind < 0.4:
                prompt = (shared[:int(rng.integers(32, 128))]
                          + rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(1, 20)))
                          .tolist())
            elif kind < 0.6:
                prompt = rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(150, 400))).tolist()
            else:
                prompt = rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(3, 40))).tolist()
            n = int(rng.integers(1, 24))
            entry = [eng.submit(prompt, max_new_tokens=n), prompt, n,
                     False]
            live.append(entry)
            submitted += 1
        if live and rng.random() < 0.05:
            victim = live[int(rng.integers(len(live)))]
            if not victim[3]:
                eng.cancel(victim[0].request_id)
                victim[3] = True
        eng.step()
        for e in list(live):
            if e[0].done.is_set():
                live.remove(e)
                done.append(e)
    for _ in range(3000):
        if all(e[0].done.is_set() for e in live):
            break
        eng.step()
    done += live
    assert all(e[0].done.is_set() for e in done)
    exact = 0
    for req, prompt, n, cancelled in done:
        if req.status != "completed":
            assert cancelled and req.status == "cancelled", req.status
            continue
        assert len(req.output) <= n
        # Unchunked, un-shared requests are token-exact vs solo; shared/
        # chunked ones round unlike a whole-prompt prefill (greedy
        # near-ties may flip) — length is still pinned.
        if req.prefix_len == 0 and len(prompt) <= 64 \
                and req.prompt_len == len(prompt):
            assert req.output == _solo(params, cfg, prompt, n)
            exact += 1
    assert exact >= 5  # the filler class really was exercised
    s = eng.stats()
    assert s["pages_in_use"] == s["pages_pinned"], "leaked pages"
    assert s["prefill_backlog_tokens"] == 0, "backlog accounting drifted"
    assert s["active"] == s["prefilling"] == s["queued"] == 0
    eng.shutdown()


# ------------------------------------------------------ stats plumbing


def test_paged_stats_and_replica_metrics_plumbing():
    """pages_free / pages_pinned / kv_fragmentation / prefill-backlog
    flow engine.stats() -> replica_metrics() (the dict the controller
    snapshots into serve.status()), and `load` counts prefill-backlog
    tokens, not just queue depth."""
    from ray_tpu.serve.decode import DecodeEngine, LlamaDecodeDeployment

    cfg, params = _tiny(max_seq_len=512)
    rng = np.random.default_rng(10)
    eng = DecodeEngine(params, cfg, slots=1, capacity=256, page_tokens=16,
                       prefix_pool_entries=0, prefill_chunk_tokens=32)
    active = eng.submit(rng.integers(0, cfg.vocab_size, 10).tolist(),
                        max_new_tokens=40)
    eng.step()
    # One active slot; a long prompt queued behind it = prefill backlog.
    queued_long = eng.submit(
        rng.integers(0, cfg.vocab_size, 200).tolist(), max_new_tokens=2)
    s = eng.stats()
    assert s["active"] == 1 and s["queued"] == 1
    assert s["prefill_backlog_tokens"] == 200
    assert s["load"] == 1 + 1 + 200 // 32  # active + queued + backlog
    assert s["pages_total"] == eng.pool_pages
    assert s["pages_free"] + s["pages_in_use"] == s["pages_total"]
    assert 0.0 <= s["kv_fragmentation"] <= 1.0
    _drive(eng, [active, queued_long])
    assert eng.stats()["prefill_backlog_tokens"] == 0
    eng.shutdown()

    dep = object.__new__(LlamaDecodeDeployment)
    dep.engine = DecodeEngine(params, cfg, slots=1, capacity=64,
                              page_tokens=16, prefix_pool_entries=4)
    m = dep.replica_metrics()
    for key in ("load", "queued", "prefill_backlog_tokens", "pages_total",
                "pages_free", "pages_in_use", "pages_pinned",
                "kv_fragmentation", "preempted", "prefixes"):
        assert key in m, key
    dep.engine.shutdown()


def test_paged_rejects_bad_geometry():
    from ray_tpu.serve.decode import DecodeEngine

    cfg, params = _tiny()
    with pytest.raises(ValueError, match="multiple"):
        DecodeEngine(params, cfg, slots=1, capacity=100, page_tokens=16)
    for bad in (0, -16):  # there is no unpaged engine to fall back to
        with pytest.raises(ValueError, match="positive"):
            DecodeEngine(params, cfg, slots=1, capacity=128,
                         page_tokens=bad)
    eng = DecodeEngine(params, cfg, slots=1, capacity=128, page_tokens=16,
                       pool_pages=4, prefix_pool_entries=0)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(1, 70)), max_new_tokens=8)  # > 4 pages
    eng.shutdown()
