"""``ops/paged_decode_attention.py`` in the Pallas interpreter against a plain
float32 gather-and-softmax: the kernel copies each live page out of the
pool itself, skips the pages nobody sees, adds a slot's lists up in its
output block and stops at the last live list. Pages of 16 tokens, 4 query
heads over 2 key heads of 16 lanes, flat; and the ONE-POOL form, a row key
and value at once (a latent row), at a toy width and at DeepSeek-V2's 128
query rows over 640 lanes with the first 512 the value."""

import numpy as np
import pytest

T, H, KV, D = 16, 4, 2, 16
W = KV * D
SCALE = 0.25
# ``(query rows, lanes, value lanes or None = a value pool, scale)``: the
# toy latent row's value is not whole lane tiles, so the kernel weighs the
# whole row and the wrapper cuts; 512 of 640 are, so it weighs those.
FORMS = {
    "two pools": (H, W, None, SCALE),
    "one pool": (H, W, 24, SCALE),
    "one pool, 128 x 640": (128, 640, 512, 640 ** -0.5),
}
TWO, TOY, WIDE = FORMS


def _case(name):
    """``(lists, owner, index, pos, window)`` of a named layout; every
    page id is distinct, and no live page is page 0."""
    from ray_tpu.models import moe_decode

    fresh = iter(range(1, 10_000))

    def table(counts):
        t = np.zeros((len(counts), max(max(counts), 1)), np.int32)
        for s, n in enumerate(counts):
            t[s, :n] = [next(fresh) for _ in range(n)]
        return t

    if name in ("groups", "groups, G 9"):
        # Slot 0: 21 pages, the last ONE token full (two groups, 11 rows
        # of padding); slot 1 idle; slot 2: 5 pages, its last page full;
        # two lists of nobody behind them.
        G = 16 if name == "groups" else 9
        counts = np.asarray([21, 0, 5])
        padded = -(-counts // G) * G
        rows = int(padded.sum()) + 2 * G
        slot, index = np.nonzero(np.arange(padded.max())[None, :]
                                 < padded[:, None])
        view = np.zeros((3, rows), np.int32)
        view[1] = -1
        tab = table(counts)
        view[0, :len(slot)] = np.where(
            index < counts[slot],
            tab[slot, np.minimum(index, tab.shape[1] - 1)], 0)
        view[1, :len(slot)], view[2, :len(slot)] = slot, index
        if G == 16:     # the engine's own builder makes the same list
            assert np.array_equal(view, moe_decode.live_page_view(
                tab, counts, rows))
        return (view[0].reshape(-1, G), view[1].reshape(-1, G)[:, 0],
                view[2].reshape(-1, G),
                np.asarray([20 * T, 7, 5 * T - 1], np.int32), None)
    if name == "interleaved":
        # Two slots' groups with lists of nobody between and round them,
        # and a slot (1) between them that owns none.
        owner = np.asarray([-1, 0, -1, 0, 2, -1, 2, -1], np.int32)
        index = np.tile(np.arange(16, dtype=np.int32), (8, 1))
        index[3] += 16
        index[6] += 16
        lists = np.asarray([[next(fresh) for _ in range(16)]
                            for _ in range(8)], np.int32)
        return (lists, owner, index,
                np.asarray([29 * T + 3, 0, 17 * T], np.int32), None)
    if name == "window":
        # A list a slot of its last 9 pages under a window of 100 tokens:
        # slot 0 far along, slot 1 with 2 pages so far, slot 2 not
        # stepping, slot 3 on a page's last token.
        held = np.asarray([40, 2, 0, 12])
        tab = table(list(held))
        view = moe_decode.window_page_view(
            tab, np.zeros(4, np.int32), held, 9)
        return (view[0], np.arange(4, dtype=np.int32), view[1],
                np.asarray([39 * T + 5, T + 2, 0, 12 * T - 1], np.int32),
                100)
    if name == "nobody":
        return (np.zeros((3, 16), np.int32), np.full(3, -1, np.int32),
                np.zeros((3, 16), np.int32), np.zeros(2, np.int32), None)
    raise KeyError(name)


def _reference(q, k, v, lists, owner, index, pos, window, form=TWO):
    """Each owner's queries over every token it sees, float64:
    ``(sees (B,), out (B, H, Wv))`` with ``out = softmax(q k) v`` over all
    the value's lanes (``v`` None: the first of the key's)."""
    scale = FORMS[form][3]
    if v is None:
        v = k[..., :FORMS[form][2]]
    B = q.shape[0]
    out = np.zeros(q.shape[:2] + v.shape[-1:], np.float32)
    sees = np.zeros(B, bool)
    for b in range(B):
        keys, values = [], []
        for i in np.nonzero(owner == b)[0]:
            for g in range(lists.shape[1]):
                if index[i, g] < 0 or lists[i, g] < 0:
                    continue
                for t in range(T):
                    at = index[i, g] * T + t
                    if at <= pos[b] and (window is None
                                         or at > pos[b] - window):
                        keys.append(k[lists[i, g], t])
                        values.append(v[lists[i, g], t])
        if not keys:
            continue
        sees[b] = True
        s = q[b].astype(np.float64) @ np.asarray(keys, np.float64).T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ np.asarray(values,
                                                             np.float64)
    return sees, out


def _inputs(case, dtype, pool_pages=128, seed=0, form=TWO):
    """``v`` is None in a one-pool form: the keys' first lanes."""
    import jax.numpy as jnp

    lists, owner, index, pos, window = _case(case)
    rng = np.random.default_rng(seed)
    lists = np.where(lists > 0, lists % (pool_pages - 1) + 1, lists)
    B = len(pos)
    heads, lanes, value_lanes, _ = FORMS[form]
    q, k, v = (np.array(jnp.asarray(
        rng.normal(size=shape), jnp.float32).astype(dtype).astype(
            jnp.float32)) for shape in ((B, heads, lanes),
                                        (pool_pages, T, lanes),
                                        (pool_pages, T, lanes)))
    return (q, k, v if value_lanes is None else None, lists, owner, index,
            pos, window)


def _run(q, k, v, lists, owner, index, pos, window, dtype, form=TWO):
    import jax
    import jax.numpy as jnp

    m, l, acc = jax.jit(_attend(window, form))(
        jnp.asarray(q, dtype), jnp.asarray(k, dtype),
        None if v is None else jnp.asarray(v, dtype),
        jnp.asarray(lists), jnp.asarray(owner), jnp.asarray(index),
        jnp.asarray(pos))
    return np.asarray(m), np.asarray(l), np.asarray(acc)


def _attend(window, form=TWO):
    """The op as a model calls it: the lists from the view, then the
    kernel (with no value pool, told which lanes of a row are its
    value)."""
    from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                    paged_decode_attention)

    _, _, value_lanes, scale = FORMS[form]

    def run(q, k, v, lists, owner, index, pos):
        return paged_decode_attention(
            q, k, v, page_lists(lists, owner, index, pos, T, window), scale,
            value_width=value_lanes)
    return run


CASES = ["groups", "groups, G 9", "interleaved", "window", "nobody"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case,form", [(c, TWO) for c in CASES]
                         + [(c, TOY) for c in CASES]
                         + [(c, WIDE) for c in ("groups", "interleaved")])
def test_kernel_matches_plain_attention(case, form, dtype):
    """Lists with padding pages, a list of nobody, a last page one token
    full, ``G`` 9 and 16, a window, two slots whose groups lie between
    lists of nobody; pools in float32 and bfloat16 (the reference reads
    the rounded pool in float64: what is left is the probabilities'
    rounding for the value product); a key pool and a value pool, and one
    pool whose rows are both."""
    *args, window = _inputs(case, dtype, form=form)
    m, l, acc = _run(*args, window, dtype, form)
    sees, want = _reference(*args, window, form)
    assert acc.shape == want.shape
    assert np.array_equal(l.max(-1) > 0, sees)
    assert np.all(l[~sees] == 0) and np.all(m[~sees] == -1e30)
    got = acc[sees] / l[sees][..., None]
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert np.isfinite(got).all()
    assert np.abs(got - want[sees]).max(initial=0.0) < tol


@pytest.mark.parametrize("block_pages", [1, 3, 16])
def test_sub_blocks_of_any_size_give_the_same_answer(monkeypatch,
                                                     block_pages):
    """``BLOCK_PAGES`` that divide a list, do not, and cover it whole: the
    running softmax across sub-blocks and across a slot's lists."""
    from ray_tpu.ops import paged_decode_attention as pda

    monkeypatch.setattr(pda, "BLOCK_PAGES", block_pages)
    blocks, each = pda._blocks(16, T)
    assert blocks * each >= 16 and (each * T) % 128 == 0
    for case in ("groups", "window"):
        *args, window = _inputs(case, "float32", seed=block_pages)
        _, l, acc = _run(*args, window, "float32")
        sees, want = _reference(*args, window)
        assert np.abs(acc[sees] / l[sees][..., None]
                      - want[sees]).max() < 2e-5


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("case", ["groups", "window"])
def test_a_page_nobody_sees_is_never_read(case, form):
    """Every pool page that no query may see (the scratch page the padding
    rows name, the pages of other sequences) holds NaN: a copy of one, or a
    stale value under a zero weight, would show. In a one-pool form the
    wiped buffer is the keys' own."""
    q, k, v, lists, owner, index, pos, window = _inputs(case, "float32",
                                                        form=form)
    live = np.zeros(len(k), bool)
    for i, o in enumerate(owner):
        for g in range(lists.shape[1]):
            first = index[i, g] * T
            if o >= 0 and index[i, g] >= 0 and first <= pos[o] and (
                    window is None or first + T - 1 > pos[o] - window):
                live[lists[i, g]] = True
    assert not live[0] and 0 in lists     # padding names the scratch page
    sees, want = _reference(q, k, v, lists, owner, index, pos, window, form)
    k[~live] = np.nan
    if v is not None:
        v[~live] = np.nan
    _, l, acc = _run(q, k, v, lists, owner, index, pos, window, "float32",
                     form)
    got = acc[sees] / l[sees][..., None]
    assert np.isfinite(got).all() and np.abs(got - want[sees]).max() < 2e-5


def test_partials_a_list_add_up_to_the_slots():
    """The kernel underneath (``_partials``) with an output row a list:
    every visited list's own maximum, sum and weighted values, which add
    up to what the kernel adds up itself when a slot's lists share a
    row."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_decode_attention as pda

    q, k, v, lists, owner, index, pos, _ = _inputs("groups", "float32")
    n = len(owner)
    plan = pda.page_lists(*(jnp.asarray(a) for a in (lists, owner, index,
                                                     pos)), T)
    assert int(plan.count) == 3 and plan.rows.tolist() == [0, 0, 2, 2, 2]
    m, l, acc = (np.asarray(a) for a in pda._partials(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), plan,
        jnp.arange(n), n, SCALE))
    _, l_all, acc_all = _run(q, k, v, lists, owner, index, pos, None,
                             "float32")
    # Slot 0 owns lists 0 and 1, slot 2 list 2; lists 3 and 4 are nobody's
    # and behind the last live one: not visited.
    top = np.maximum(m[0], m[1])
    w0, w1 = np.exp(m[0] - top), np.exp(m[1] - top)
    assert np.allclose(w0 * l[0] + w1 * l[1], l_all[0][:, None], rtol=1e-6)
    assert np.allclose(w0 * acc[0] + w1 * acc[1], acc_all[0], rtol=1e-5,
                       atol=1e-6)
    assert np.allclose(acc[2], acc_all[2], rtol=1e-6)


@pytest.mark.parametrize("form", list(FORMS))
def test_the_grid_ends_at_the_last_live_list(form):
    """What is visited is a traced count: the same compiled program serves
    a view whose live lists end early, and writes nothing for the lists
    behind them (``l`` 0 for a slot whose only list lies there)."""
    import jax
    import jax.numpy as jnp

    q, k, v, lists, owner, index, pos, _ = _inputs("interleaved", "float32",
                                                   form=form)
    run = jax.jit(_attend(None, form))
    fixed = [None if a is None else jnp.asarray(a) for a in (q, k, v, lists)]
    _, l, _ = run(*fixed, jnp.asarray(owner), jnp.asarray(index),
                  jnp.asarray(pos))
    assert (np.asarray(l)[[0, 2]] > 0).all()
    # Slot 2's pages now lie past its position: nothing of it is live.
    early = pos.copy()
    early[2] = -1
    _, l, acc = run(*fixed, jnp.asarray(owner), jnp.asarray(index),
                    jnp.asarray(early))
    assert run._cache_size() == 1
    l = np.asarray(l)
    assert (l[0] > 0).all() and (l[1:] == 0).all()
    sees, want = _reference(q, k, v, lists, owner, index, early, None, form)
    assert np.abs(np.asarray(acc)[0] / l[0][:, None] - want[0]).max() < 2e-5


@pytest.mark.parametrize("form,v,value_width", [(TWO, "keys", 24),
                                                (TOY, None, None)])
def test_the_values_are_a_pool_or_lanes_of_the_keys_not_both_or_neither(
        form, v, value_width):
    """Which form runs is what the caller passed; a call that says both,
    or neither, is refused before anything is traced."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                    paged_decode_attention)

    q, k, _, lists, owner, index, pos, _ = _inputs("groups", "float32",
                                                   form=form)
    plan = page_lists(*(jnp.asarray(a) for a in (lists, owner, index, pos)),
                      T)
    with pytest.raises(ValueError, match="one of the two"):
        paged_decode_attention(jnp.asarray(q), jnp.asarray(k),
                               None if v is None else jnp.asarray(k), plan,
                               SCALE, value_width=value_width)
