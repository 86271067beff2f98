"""Pallas TPU kernel for a DECODE step's attention over a paged cache, read
where the pages lie: the kernel is handed the pool's key and value leaves
whole and, for every query row, LISTS of page ids; it copies each live page
from the pool into VMEM itself, inside the program that scores it, so no
program gathers pages into a contiguous copy first.

* A **list** is ``G`` pages of one owner (a slot): a window layer hands in
  one list a slot (its last ``keep`` pages), a full layer the groups of
  ``VIEW_GROUP`` pages that ``moe_decode.live_page_view`` builds. A grid
  step owns one list. It starts the copies of the NEXT list's live pages
  into the other half of a double buffer, waits for its own and scores
  them in sub-blocks of ``BLOCK_PAGES`` pages; a sub-block none of whose
  pages is live costs nothing, a list with none is a step that does
  nothing, and the lists behind the last live one are not visited (the
  grid's length is a traced number).
* A page that holds no token its owner may see is **not fetched**: the
  padding of a slot's last group, the window pages a short sequence does
  not have, the rows past the list. Its tokens are masked and its stale
  values zeroed (0 x stale is not 0 if stale is not finite).
* Heads lie FLAT on the last axis, as the models cache them: the queries
  come in as ``(H, W)`` rows over ALL key heads' lanes (zero but on a
  head's own, ``phi4flash_decode._flat_queries``), a score is one matmul
  against a page as it lies, and the weighted values come back over all
  ``W`` lanes for the caller to pick each head's own from.
* The lists of one owner that follow each other are added up IN the
  kernel: the running maximum, sum and weighted values live in the output
  block, which stays in VMEM while the owner does not change.
* **One pool or two.** Called with a key pool and a value pool (the
  models that cache K and V), a grid step copies a live page from each.
  Called with NO value pool and the lanes of a key row that are its value
  (a latent row, ``models/deepseek_decode.py``: the compressed key-value
  is key and value at once), it copies a page ONCE into one buffer, scores
  the whole row and weighs its first lanes. Which runs is what the caller
  passed. One copy a page is short enough that the kernel's own
  instructions bound it, not the copies, so that form scores a list whole
  (a sub-block's fixed cost is paid once) and walks the dead pages only
  in a list that has one; the two-pool program is copy-bound and is
  letter for letter what it was.

Roundings: scores float32 from the cache dtype's operands, x ``scale``,
masked to ``-1e30``; exponentials and their sum float32; probabilities
rounded to the cache's dtype for the value product; float32 accumulation.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "paged_decode_attn"
_MASKED = -1e30
_LANE = 128
# Pages scored together: one wait, two matmuls. On a v5e 4, 8 and 16 read
# within 3% of each other (``microbench_paged.py``; PERF.md section 5 has
# the table); at 8 a dead half of a slot's last group of 16 is skipped.
BLOCK_PAGES = 8
_VMEM_DEFAULT = 16 * 2 ** 20   # the scoped limit a call gets unasked (v5e)


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernel runs in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


def _blocks(pages: int, page_tokens: int) -> Tuple[int, int]:
    """``(sub-blocks, pages a sub-block)`` of a list of ``pages``: as few
    sub-blocks as ``BLOCK_PAGES`` allows, all the same size, each a whole
    number of lanes of tokens (a list is padded up to them with pages that
    are never fetched)."""
    unit = _LANE // math.gcd(_LANE, page_tokens)   # pages to whole lanes
    count = max(1, min(-(-pages // BLOCK_PAGES), -(-pages // unit)))
    each = -(-pages // count)
    return count, -(-each // unit) * unit


def _kernel(pages_ref, q_rows_ref, out_rows_ref, first_ref, q_ref, seen_ref,
            *refs, scale, page_tokens, blocks, block_pages, value_lanes):
    # One pool (``value_lanes`` set): the values are the first lanes of
    # the key rows, so a page is copied once and lies in one buffer.
    one_pool = value_lanes is not None
    pools = 1 if one_pool else 2
    hbms, (m_ref, l_ref, acc_ref) = refs[:pools], refs[pools:pools + 3]
    bufs, sems = refs[pools + 3:-1], refs[-1]
    k_buf, v_buf = bufs[0], bufs[-1]
    i = pl.program_id(0)
    T, G = page_tokens, blocks * block_pages
    half = i % 2

    def page(j, g):
        return pages_ref[j * G + g]

    def copies(j, into, g):
        """The copies of page ``g`` of list ``j``, one a pool (keys,
        values)."""
        src = jnp.maximum(page(j, g), 0) + first_ref[0]
        rows = pl.ds(pl.multiple_of(g * T, T), T)
        return [pltpu.make_async_copy(
            hbm.at[src], buf.at[into, rows],
            sems.at[side, into, g // block_pages])
            for side, (hbm, buf) in enumerate(zip(hbms, bufs))]

    def each_page(j, first, last, live, then):
        """``then(g)`` for the pages ``first <= g < last`` of list ``j``
        that are live, or that are not."""
        def one(g, _):
            @pl.when((page(j, g) >= 0) == live)
            def _():
                then(g)
            return 0
        jax.lax.fori_loop(first, last, one, 0)

    def start(j, into):
        def go(g):
            for copy in copies(j, into, g):
                copy.start()
        each_page(j, 0, G, True, go)

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        start(i + 1, 1 - half)

    before = jnp.maximum(i - 1, 0)

    @pl.when((i == 0) | (out_rows_ref[i] != out_rows_ref[before]))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def wait(g):
        for copy in copies(i, half, g):
            copy.wait()

    def wipe(g):
        v_buf[half, pl.ds(pl.multiple_of(g * T, T), T)] = jnp.zeros(
            (T, v_buf.shape[-1]), v_buf.dtype)

    for b in range(blocks):
        first, last = b * block_pages, (b + 1) * block_pages
        live = jax.lax.fori_loop(
            first, last,
            lambda g, n: n + (page(i, g) >= 0).astype(jnp.int32), 0)

        @pl.when(live > 0)
        def _block():
            each_page(i, first, last, True, wait)
            if one_pool:    # most lists have no dead page to wipe
                pl.when(live < block_pages)(
                    lambda: each_page(i, first, last, False, wipe))
            else:
                each_page(i, first, last, False, wipe)
            rows = pl.ds(first * T, block_pages * T)
            k = k_buf[half, rows]
            v = k[:, :value_lanes] if one_pool else v_buf[half, rows]
            seen = seen_ref[0, b:b + 1, :] != 0
            s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * scale, _MASKED)
            m_prev = m_ref[0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # A row that has seen no key yet stands at ``_MASKED``, where a
            # masked score's exponential would be 1.
            e = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            shrink = jnp.exp(m_prev - m_new)
            l_ref[0] = l_ref[0] * shrink + jnp.sum(e, axis=-1,
                                                   keepdims=True)
            m_ref[0] = m_new
            acc_ref[0] = acc_ref[0] * shrink + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


class PageLists(NamedTuple):
    """A step's lists of pages as the kernel reads them (``page_lists``):
    built once from the step's view, read by every layer's call."""
    pages: jax.Array    # (n x G',) int32 pool rows, -1 = not fetched
    seen: jax.Array     # (n, sub-blocks, tokens a sub-block) int32 0 / 1
    rows: jax.Array     # (n,) int32 whose queries, and whose output row
    count: jax.Array    # () int32 the lists visited
    sees: jax.Array     # (B,) bool the owners that see any token


def page_lists(pages, owner, index, pos, page_tokens: int,
               window: Optional[int] = None) -> PageLists:
    """``pages`` (n, G) int32 the pool rows of ``n`` lists (-1 = no page),
    ``owner`` (n,) whose each list is (-1 = nobody's), ``index`` (n, G)
    each page's index in its owner's sequence (-1 = no page), ``pos`` (B,)
    the position of each owner's query. The token ``t`` of a page at index
    ``x`` sits at position ``x T + t`` and is seen if that is at most
    ``pos[owner]`` and, under ``window``, more than ``pos[owner] -
    window``; a page with no such token is not fetched. The lists of one
    owner must follow each other (lists of nobody may lie anywhere); the
    visits end at the last list that holds a live page."""
    T = page_tokens
    n, G = pages.shape
    mine = owner >= 0
    whose = jnp.maximum(owner, 0).astype(jnp.int32)
    at = pos[whose][:, None, None]                             # (n, 1, 1)
    tok = index[:, :, None] * T + jnp.arange(T)[None, None, :]  # (n, G, T)
    seen = ((mine[:, None] & (index >= 0) & (pages >= 0))[:, :, None]
            & (tok <= at))
    if window is not None:
        seen &= tok > at - window
    live = seen.any(-1)                                        # (n, G)
    order = jnp.arange(n, dtype=jnp.int32)
    blocks, block_pages = _blocks(G, T)
    pad = ((0, 0), (0, blocks * block_pages - G))
    return PageLists(
        pages=jnp.pad(jnp.where(live, pages, -1).astype(jnp.int32), pad,
                      constant_values=-1).reshape(-1),
        seen=jnp.pad(seen, pad + ((0, 0),)).reshape(
            n, blocks, block_pages * T).astype(jnp.int32),
        # A list of nobody adds to the row before it.
        rows=whose[jax.lax.cummax(jnp.where(mine, order, 0))],
        count=jnp.max(jnp.where(live.any(-1), order + 1, 0)),
        sees=jnp.zeros(pos.shape, bool).at[whose].max(live.any(-1)))


def _partials(q, k_pool, v_pool, lists: PageLists, out_rows, n_out: int,
              scale: float, first_page=0, value_width: Optional[int] = None):
    """The kernel: list ``i`` reads the queries ``q[lists.rows[i]]`` and
    adds to the output row ``out_rows[i]`` (equal ``out_rows`` adjacent).
    Returns the ``n_out`` rows' ``(m, l)`` (n_out, H, 1) and ``acc`` (n_out,
    H, W), float32; a row no visited list names is not written. With no
    ``v_pool`` the values are the first ``value_width`` lanes of the key
    rows and ``acc`` is that wide, or all ``W`` lanes wide where
    ``value_width`` is not whole lane tiles (the rest is the caller's to
    cut)."""
    if (v_pool is None) == (value_width is None):
        raise ValueError("pass a value pool, or which lanes of a key row "
                         "are its value: one of the two")
    _, H, W = q.shape
    T = k_pool.shape[1]
    seen = lists.seen
    pools, value_lanes = [k_pool, v_pool], None
    if v_pool is None:
        pools = [k_pool]
        value_lanes = value_width if value_width % _LANE == 0 else W
        # No copy hides a sub-block's fixed cost here: a list is scored
        # whole (a tenth of a layer's call at DeepSeek-V2's shape, PR 56).
        seen = seen.reshape(seen.shape[0], 1, -1)
    n, blocks, block_tokens = seen.shape
    G = blocks * block_tokens // T
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    Wv = value_lanes or W
    # Two halves of a list's pages, a pool; the query and output blocks
    # twice; the sub-block's float32 scores and exponentials.
    need = (2 * len(pools) * G * T * W * itemsize
            + 2 * H * W * (itemsize + 4)
            + 4 * H * block_tokens * 4 + (2 << 20))

    def q_map(i, pages, q_rows, out_rows, first):
        return q_rows[i], 0, 0

    def out_map(i, pages, q_rows, out_rows, first):
        return out_rows[i], 0, 0

    def vmem(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    kernel = functools.partial(_kernel, scale=scale, page_tokens=T,
                               blocks=blocks, block_pages=G // blocks,
                               value_lanes=value_lanes)
    with jax.named_scope(NAME):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(lists.count,),
                in_specs=[
                    vmem((1, H, W), q_map),
                    vmem((1, blocks, block_tokens),
                         lambda i, *_: (i, 0, 0)),
                ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
                out_specs=[vmem((1, H, 1), out_map),
                           vmem((1, H, 1), out_map),
                           vmem((1, H, Wv), out_map)],
                scratch_shapes=[
                    pltpu.VMEM((2, G * T, W), pool.dtype) for pool in pools
                ] + [pltpu.SemaphoreType.DMA((len(pools), 2, blocks))]),
            out_shape=[jax.ShapeDtypeStruct((n_out, H, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n_out, H, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n_out, H, Wv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=need if need > _VMEM_DEFAULT else None),
            interpret=_interpret(),
            name=NAME,
        )(lists.pages, lists.rows, out_rows.astype(jnp.int32),
          jnp.asarray(first_page, jnp.int32).reshape(1), q, seen, *pools)


def paged_decode_attention(q, k_pool, v_pool, lists: PageLists,
                           scale: float, first_page=0,
                           value_width: Optional[int] = None):
    """Softmax partials of each owner's queries over its lists of pages.

    ``q`` (B, H, W) in the pool's dtype, row ``b`` the queries of owner
    ``b``, each head over all ``W`` lanes; ``k_pool`` / ``v_pool`` (P, T,
    W), a page a row, row ``first_page`` (a layer's offset in a pool of
    several, traced) the page the lists call 0; ``lists`` from
    ``page_lists`` with ``T`` for ``page_tokens``. Where a row of
    ``k_pool`` is key and value at once (a latent row), pass None for
    ``v_pool`` and the ``value_width`` lanes, from 0, that are the value:
    a page is then copied once and read for both.

    Returns ``(m, l, acc)``: (B, H) the largest score seen, (B, H) the sum
    of ``exp(score - m)``, (B, H, W), or (B, H, ``value_width``), those
    weights times the values, all float32. An owner that sees nothing gets
    ``m`` -1e30 and ``l`` 0, and its rows of ``acc`` may not have been
    written: select by ``l``."""
    m, l, acc = _partials(q, k_pool, v_pool, lists, lists.rows, q.shape[0],
                          scale, first_page, value_width)
    sees = lists.sees[:, None]
    return (jnp.where(sees, m[..., 0], _MASKED),
            jnp.where(sees, l[..., 0], 0.0), acc[..., :value_width])
