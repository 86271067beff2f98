"""Kernel-alone timings of the decode step's paged attention on the chip:
``ray_tpu/ops/paged_decode_attention.py`` against the gather-then-attend it
replaced, at the two shapes ``phi-4-mini-flash.reasoning_batch`` runs.

    chiprun -- python3 microbench_paged.py                # both shapes
    chiprun -- python3 microbench_paged.py --blocks 4,8,16 --check

* **window**: 64 lists of 9 pages (one a slot), window 512, a pool of
  8 x 705 pages of 64 tokens x 1,280 lanes, bfloat16;
* **shared**: the view of 64 slots at 768-2,688 tokens of context
  (``moe_decode.live_page_view``: groups of 16 pages, some 160 live lists
  of a rung of 256, a quarter of their rows padding), a pool of 8,193
  pages.

A time is the host clock over ``--calls`` back-to-back calls closed by one
``block_until_ready``. ``share`` is the tokens a query sees x their keys'
and values' bytes over the time x 819 GB/s: the benchmark's count
(``benchmarks/phi4flash_counts.py``), padding and masked tokens not
credited. The XLA path is the parent's: a window layer's gather and
``_attend_rows``; for the shared cache one copy of the live pages (``gather``,
once a step for eight layers) and the loop over blocks of 32 groups
(``attend``, a layer). ``--partials`` times the kernel with every list's
partials written and a slot's lists added up by XLA. ``--check`` holds
kernel and XLA path to plain float32 attention. Rows go to
``chiprun_out/paged_sweep.jsonl``; nothing here runs off the chip.

``--model cohere2`` times the same kernel at Command A+'s widths instead
(``command-a-plus.grounded_docs_batch``: 24 slots, 128 query heads over 8
key heads x 128 = 1,024 flat lanes): a window layer's 65 pages a slot in
lists of 16, and the full layer's view at 8k-32k tokens of context. A query
head laid over all 1,024 lanes does 8 x the useful operations, so each row
also says what share of the bf16 peak the kernel's matmuls as executed
reach (``flat_ops_share_of_peak_pct``): near 100 the flat-lane form is
compute-bound and a grouped-head variant would pay; well under it the
bytes bound the kernel.

``--model deepseek`` times the ONE-POOL form at DeepSeek-V2's widths
(``deepseek-v2.longdocs_batch``: 26 of 32 slots at 2k-14k tokens of
context on the 4,096-row rung, 128 absorbed query rows over the 640 lanes
of a latent row that is key and value at once, values its first 512), in
the third layer of the flat pool of five, against the gather-then-attend
it replaced (``models/deepseek_decode.py`` before PR 56: a copy of the
rung's rows of pages, then XLA's einsums over it). ``share_of_hbm_pct``
credits 576 x 2 B a token a query sees, the benchmark's count
(``benchmarks/deepseek_counts.py``); ``flat_ops_share_of_peak_pct`` is 2 x
128 x (640 + 512) a FETCHED token over the bf16 peak.

``--chunk`` times the PREFILL chunk's kernel instead,
``ray_tpu/ops/chunk_attention.py``, alone at the three served models'
shapes (``--model cohere2|mimo|phi4flash|all``): Command A+'s 2,048
queries of 128 heads over 8 at prefixes 0 / 8,192 / 30,720 (the full
variant over the engine's table of 4,096 / 16,384 / 32,768 keys and over
exactly the live keys, the window variant over its 6,208), MiMo-V2.5's 64
heads over 4 / 8, keys 192 against values 128, a window of 128 with a
sink, phi-4-mini-flash's pairs under a window of 512. ``share`` is there
the live (query, key) pairs x 2 x heads x (D + Dv) over the time x 197
TFLOP/s, the benchmark's count (``benchmarks/cohere2_moe_counts.py``).
``--against name=path`` (repeatable) times another copy of the module,
the parent's or an ablated one, beside the tree's in the same process;
``--tiles QxK`` and ``--group g`` override what the tree's module would
choose from the shape (a sweep); rows go to
``chiprun_out/chunk_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import moe_decode, phi4flash
from ray_tpu.models import phi4flash_decode as pd
from ray_tpu.ops import paged_decode_attention as pda

HBM = 819e9
T, G = 64, moe_decode.VIEW_GROUP
# The cell's layout: 64 slots, 704 window pages a layer, 8,192 full pages.
SLOTS, WINDOW_POOL, SHARED_POOL, RUNG = 64, 705, 8193, 4096
WINDOW_LAYER = 3    # the layer of the flat window pool the lists point into


def _time(fn, args, calls):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def _pool(key, pages, c, dtype):
    return jax.random.normal(key, (pages, T, c.kv_width),
                             jnp.float32).astype(dtype)


def _window_case(c, rng):
    """One list a slot of its last ``keep`` window pages, a slot at
    position ``pos``: ``(lists, owner, index, pos)``."""
    keep = -(-(c.window - 1) // T) + 1
    pos = rng.integers(600, 6000, size=SLOTS).astype(np.int32)
    held = pos // T + 1
    # A table from the sequence's page 0 on, its last ``keep`` filled.
    table = np.zeros((SLOTS, int(held.max())), np.int32)
    free = rng.permutation(np.arange(1, WINDOW_POOL)).tolist()
    for s in range(SLOTS):
        for i in range(held[s] - keep, held[s]):
            table[s, i] = free.pop()
    view = moe_decode.window_page_view(table, np.zeros(SLOTS, np.int32),
                                       held, keep)
    return (view[0] + WINDOW_LAYER * WINDOW_POOL,
            np.arange(SLOTS, dtype=np.int32), view[1], pos)


def _shared_case(rng):
    pos = rng.integers(768, 2688, size=SLOTS).astype(np.int32)
    counts = pos // T + 1
    free = rng.permutation(np.arange(1, SHARED_POOL))
    table = np.zeros((SLOTS, int(counts.max())), np.int32)
    at = 0
    for s in range(SLOTS):
        table[s, :counts[s]] = free[at:at + counts[s]]
        at += counts[s]
    view = moe_decode.live_page_view(table, counts, RUNG)
    return (view[0].reshape(-1, G), view[1].reshape(-1, G)[:, 0],
            view[2].reshape(-1, G), pos)


def _seen(owner, index, pos, window, xp=np):
    """(n, G, T) bool: the op's validity rule, on the host or (``xp`` =
    ``jnp``) on traced arrays."""
    tok = index[:, :, None] * T + xp.arange(T)[None, None, :]
    at = pos[xp.maximum(owner, 0)][:, None, None]
    seen = (owner >= 0)[:, None, None] & (index >= 0)[:, :, None] \
        & (tok <= at)
    if window is not None:
        seen &= tok > at - window
    return seen


def _kernel(c, window):
    def run(q, k, v, lists, owner, index, pos):
        _, total, part = pda.paged_decode_attention(
            pd._flat_queries(q, c), k, v,
            pda.page_lists(lists, owner, index, pos, T, window),
            c.softmax_scale)
        return pd._own_values(part, c) / jnp.where(
            total > 0, total, 1.0)[..., None]
    return jax.jit(run)


def _kernel_partials(c, window):
    """Every list's partials out of the kernel, a slot's lists added up
    outside it (the 0/1 matrix at ``highest``)."""
    def run(q, k, v, lists, owner, index, pos):
        n, B = owner.shape[0], q.shape[0]
        plan = pda.page_lists(lists, owner, index, pos, T, window)
        order = jnp.arange(n, dtype=jnp.int32)
        m, l, acc = pda._partials(pd._flat_queries(q, c), k, v, plan, order,
                                  n, c.softmax_scale)
        visited = (order < plan.count)[:, None]
        m = jnp.where(visited, m[..., 0], -1e30)
        l = jnp.where(visited, l[..., 0], 0.0)
        part = jnp.where(visited[..., None], pd._own_values(acc, c), 0.0)
        mine = (owner[None, :] == jnp.arange(B)[:, None])     # (B, n)
        top = jnp.max(jnp.where(mine[:, :, None], m[None], -1e30), axis=1)
        w = jnp.where(mine[:, :, None],
                      jnp.exp(m[None] - top[:, None]), 0.0)   # (B, n, H)
        high = jax.lax.Precision.HIGHEST
        total = jnp.einsum("bnh,nh->bh", w, l, precision=high)
        out = jnp.einsum("bnh,nhd->bhd", w, part, precision=high)
        return out / jnp.where(total > 0, total, 1.0)[..., None]
    return jax.jit(run)


def _xla_window(c):
    def run(q, k, v, lists, owner, index, pos):
        B, R = lists.shape
        kk = k[lists].reshape(B, R * T, c.kv_width)
        vv = v[lists].reshape(B, R * T, c.kv_width)
        w_pos = (index[:, :, None] * T
                 + jnp.arange(T)[None, None, :]).reshape(B, R * T)
        back = pos[:, None] - w_pos
        seen = ((jnp.repeat(index, T, axis=1) >= 0) & (back >= 0)
                & (back < c.window))
        return pd._attend_rows(q, kk, vv, seen, c)
    return jax.jit(run)


def _xla_shared(c):
    """The parent's ``paged_decode_step``: ``gather`` copies the live
    blocks' pages once, ``attend`` is one reading layer."""
    block = min(RUNG // G, 32)      # groups read at a time
    def live_blocks(owner):
        return -(-jnp.sum(owner >= 0) // block)

    def gather(k, v, lists, owner):
        n = owner.shape[0]
        flat = lists.reshape(-1)

        def copy(i, both):
            rows = jax.lax.dynamic_slice_in_dim(flat, i * block * G,
                                                block * G)
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                rows_of, leaf[rows].reshape(block, G * T, c.kv_width),
                i * block, 0) for rows_of, leaf in zip(both, (k, v)))

        return jax.lax.fori_loop(
            0, live_blocks(owner), copy, tuple(
                jax.lax.empty((n, G * T, c.kv_width), k.dtype)
                for _ in range(2)))

    def attend(q, k_list, v_list, owner, index, pos):
        B, H = q.shape[0], c.n_heads
        n = owner.shape[0]
        valid = ((owner >= 0)[:, None, None]
                 & (index[:, :, None] * T + jnp.arange(T)[None, None, :]
                    <= pos[jnp.maximum(owner, 0)][:, None, None]))
        valid = valid.reshape(n, 1, G * T)
        of_group = jnp.maximum(owner, 0)
        mine = owner[None, :] == jnp.arange(B)[:, None]
        high = jax.lax.Precision.HIGHEST
        q_flat = pd._flat_queries(q, c)

        def one(i, carry):
            top, total, acc = carry
            g0 = i * block
            whose = jax.lax.dynamic_slice_in_dim(of_group, g0, block)
            seen = jax.lax.dynamic_slice_in_dim(valid, g0, block)
            part_of = jax.lax.dynamic_slice_in_dim(mine, g0, block, 1)
            k = jax.lax.dynamic_slice_in_dim(k_list, g0, block)
            v = jax.lax.dynamic_slice_in_dim(v_list, g0, block)
            s = jnp.einsum("ghc,gtc->ght", q_flat[whose], k,
                           preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * c.softmax_scale, -1e30)
            new = jnp.maximum(top, jnp.max(jnp.where(
                part_of[:, :, None], s.max(-1)[None], -1e30), axis=1))
            e = jnp.where(seen, jnp.exp(s - new[whose][..., None]), 0.0)
            part = pd._own_values(jnp.einsum(
                "ght,gtc->ghc", e.astype(v.dtype), v,
                preferred_element_type=jnp.float32), c)
            adds = part_of.astype(jnp.float32)
            shrink = jnp.exp(top - new)
            total = total * shrink + jnp.einsum(
                "bg,gh->bh", adds, e.sum(-1), precision=high)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bg,ghd->bhd", adds, part, precision=high)
            return new, total, acc

        _, total, acc = jax.lax.fori_loop(
            0, live_blocks(owner), one,
            (jnp.full((B, H), -1e30, jnp.float32),
             jnp.zeros((B, H), jnp.float32),
             jnp.zeros((B, H, 2 * c.head_dim), jnp.float32)))
        return acc / jnp.where(total > 0.0, total, 1.0)[..., None]

    return jax.jit(gather), jax.jit(attend)


def _reference(c, q, k, v, lists, owner, seen):
    """Plain float32 attention of every slot over the tokens it sees, a
    slot at a time on the device: (B, H, 2 D)."""
    own, pair = pd._head_maps(c)

    @jax.jit
    def slot(qb, kk, vv, ok):
        qh = qb.reshape(c.n_heads, c.head_dim).astype(jnp.float32)
        kh = kk.reshape(-1, c.n_kv_heads, c.head_dim).astype(jnp.float32)
        vh = vv.reshape(-1, c.kv_pairs, 2 * c.head_dim).astype(jnp.float32)
        high = jax.lax.Precision.HIGHEST
        s = jnp.einsum("hd,tkd,hk->ht", qh, kh, jnp.asarray(own),
                       precision=high) * c.softmax_scale
        s = jnp.where(ok[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ht,tjd,hj->hd", p, vh, jnp.asarray(pair),
                          precision=high)

    out = []
    for b in range(q.shape[0]):
        mine = np.nonzero(owner == b)[0]
        rows = lists[mine].reshape(-1)
        out.append(slot(q[b], k[rows], v[rows],
                        jnp.asarray(seen[mine].reshape(-1))))
    return jnp.stack(out)


def cohere2(args, emit):
    """The kernel at Command A+'s widths (module docstring)."""
    from ray_tpu.models import cohere2_moe
    from ray_tpu.models import cohere2_moe_decode as cd

    c = cohere2_moe.Cohere2MoeConfig()
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 4)
    slots, keep = 24, c.window // T + 1
    window_pool, full_pool = slots * keep + 6 * 32 + 1, 9217
    pos = rng.integers(8192, 32000, size=slots).astype(np.int32)
    held = pos // T + 1

    def table(pool, first):
        out = np.zeros((slots, int(held.max())), np.int32)
        free = rng.permutation(np.arange(1, pool)).tolist()
        for s_ in range(slots):
            for i in range(first[s_], held[s_]):
                out[s_, i] = free.pop()
        return out

    w_first = np.maximum(0, pos - c.window + 1) // T
    w = moe_decode.window_page_view(table(window_pool, w_first), w_first,
                                    held - w_first, keep)
    # A slot's ``keep`` pages as whole lists of G, as the model cuts them;
    # the lists point into the third layer of the flat window pool.
    per = -(-keep // G)
    short = ((0, 0), (0, per * G - keep))
    w_index = np.pad(w[1], short, constant_values=-1).reshape(-1, G)
    w_pages = np.pad(w[0], short).reshape(-1, G) \
        + 2 * window_pool * (w_index >= 0)
    full = moe_decode.live_page_view(
        table(full_pool, np.zeros(slots, np.int32)), held, 8192)
    cases = (
        ("window", c.window, 3 * window_pool,
         (w_pages, np.repeat(np.arange(slots, dtype=np.int32), per),
          w_index, pos)),
        ("full", None, full_pool,
         (full[0].reshape(-1, G), full[1].reshape(-1, G)[:, 0],
          full[2].reshape(-1, G), pos)))
    for shape, window, pool_pages, (lists, owner, index, pos_) in cases:
        seen = _seen(owner, index, pos_, window)
        tokens = int(seen.sum())
        q = jax.random.normal(keys[0], (slots, c.n_heads, c.head_dim),
                              jnp.float32).astype(dtype)
        k = jax.random.normal(keys[1], (pool_pages, T, c.kv_width),
                              jnp.float32).astype(dtype)
        v = jax.random.normal(keys[2], (pool_pages, T, c.kv_width),
                              jnp.float32).astype(dtype)
        dev = [jnp.asarray(a) for a in (lists, owner, index, pos_)]

        def run(q, k, v, lists, owner, index, pos, window=window):
            _, total, part = pda.paged_decode_attention(
                cd._flat_queries(q, c), k, v,
                pda.page_lists(lists, owner, index, pos, T, window),
                c.softmax_scale)
            return cd._own_values(part, c) / jnp.where(
                total > 0, total, 1.0)[..., None]

        fn = jax.jit(run)
        ms = _time(fn, (q, k, v, *dev), args.calls)
        useful = tokens * 2 * c.kv_width * dtype.itemsize
        # As executed: fetched pages x (scores + values) over all lanes.
        fetched = int(seen.any(2).sum()) * T
        flat_ops = 2.0 * 2 * c.n_heads * c.kv_width * fetched
        emit({"model": "cohere2", "shape": shape, "lists": len(owner),
              "live_pages": int(seen.any(2).sum()), "tokens_seen": tokens,
              "dtype": dtype.name, "block_pages": pda.BLOCK_PAGES,
              "ms": round(ms, 4),
              "share_of_hbm_pct": round(100 * useful / (ms / 1e3) / HBM, 2),
              "flat_ops_share_of_peak_pct": round(
                  100 * flat_ops / (ms / 1e3) / 197e12, 2)})
        if args.check:
            got = np.asarray(fn(q, k, v, *dev))
            worst = top = 0.0
            for b in range(slots):
                mine = np.nonzero(owner == b)[0]
                ok = seen[mine].reshape(-1)
                rows = np.maximum(lists[mine].reshape(-1), 0)
                kk = np.asarray(k[rows], np.float32).reshape(
                    -1, c.n_kv_heads, c.head_dim)[ok]
                vv = np.asarray(v[rows], np.float32).reshape(
                    -1, c.n_kv_heads, c.head_dim)[ok]
                qq = np.asarray(q[b], np.float32).reshape(
                    c.n_kv_heads, -1, c.head_dim)
                sc = np.einsum("khd,tkd->kht", qq, kk) * c.softmax_scale
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                pr /= pr.sum(-1, keepdims=True)
                want = np.einsum("kht,tkd->khd", pr, vv).reshape(
                    c.n_heads, c.head_dim)
                worst = max(worst, float(np.abs(got[b] - want).max()))
                top = max(top, float(np.abs(want).max()))
            emit({"model": "cohere2", "shape": shape, "check": "kernel",
                  "max_abs_err": worst, "largest_value": top,
                  "ok": worst <= 2e-2 * top})
            if worst > 2e-2 * top:
                raise SystemExit(f"cohere2 {shape}: the kernel is {worst} "
                                 f"from plain float32 attention")


def deepseek(args, emit, slots=32, live=26, rung=4096, pool_pages=4097,
             context=(2048, 14336)):
    """The one-pool form at DeepSeek-V2's widths against the parent's
    gather-then-attend (module docstring); the keywords are the cell's
    layout."""
    from ray_tpu.models import deepseek as ds

    c = ds.DeepseekConfig()
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 2)
    layer = 2
    H, W, R = c.n_heads, c.latent_row, c.kv_lora_rank
    pos = np.zeros(slots, np.int32)
    pos[rng.permutation(slots)[:live]] = rng.integers(*context, size=live)
    counts = np.where(pos > 0, pos // T + 1, 0)
    table = np.zeros((slots, int(counts.max())), np.int32)
    free = rng.permutation(np.arange(1, pool_pages))
    at = 0
    for s_ in range(slots):
        table[s_, :counts[s_]] = free[at:at + counts[s_]]
        at += counts[s_]
    view = moe_decode.live_page_view(table, counts, rung)
    lists, owner, index = (view[0].reshape(-1, G),
                           view[1].reshape(-1, G)[:, 0],
                           view[2].reshape(-1, G))
    seen = _seen(owner, index, pos, None)
    tokens, fetched = int(seen.sum()), int(seen.any(2).sum()) * T
    # Rows as the model caches them: zeros past the 576 numbers in use.
    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, *shape):
        x = jax.random.normal(key, shape + (W,), jnp.float32)
        return jnp.where(jnp.arange(W) < c.latent_dim, x, 0.0).astype(dtype)

    pool = draw(keys[1], 5 * pool_pages, T)
    q = draw(keys[0], slots, H)
    base = jnp.asarray(layer * pool_pages, jnp.int32)
    dev = [jnp.asarray(a) for a in (lists, owner, index, pos)]
    scale = c.softmax_scale

    def kernel(q, pool, base, lists, owner, index, pos):
        _, total, acc = pda.paged_decode_attention(
            q, pool, None, pda.page_lists(lists, owner, index, pos, T),
            scale, base, value_width=R)
        total = total[..., None]
        return jnp.where(total > 0, acc / total, 0.0)

    def gather(pool, base, lists):
        return pool[base + lists.reshape(-1)]                # (N, T, W)

    def attend(q, lat, owner, index, pos):
        """``paged_decode_step``'s ``attend`` at the parent of PR 56."""
        n, B = owner.shape[0], q.shape[0]
        valid = _seen(owner, index, pos, None, jnp).reshape(n, 1, G * T)
        of_group = jnp.maximum(owner, 0)
        mine = owner[None, :] == jnp.arange(B)[:, None]
        high = jax.lax.Precision.HIGHEST
        lat = lat.reshape(n, G * T, W)
        s = jnp.einsum("ghr,gkr->ghk", q[of_group], lat,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, -1e30)
        top = jnp.max(jnp.where(mine[:, :, None], s.max(-1)[None], -1e30),
                      axis=1)
        e = jnp.where(valid, jnp.exp(s - top[of_group][..., None]), 0.0)
        part = jnp.einsum("ghk,gkr->ghr", e.astype(lat.dtype),
                          lat[..., :R], preferred_element_type=jnp.float32)
        adds = mine.astype(jnp.float32)
        total = jnp.einsum("bg,gh->bh", adds, e.sum(-1), precision=high)
        acc = jnp.einsum("bg,ghr->bhr", adds, part, precision=high)
        return acc / jnp.where(total > 0.0, total, 1.0)[..., None]

    def xla(q, pool, base, lists, owner, index, pos):
        return attend(q, gather(pool, base, lists), owner, index, pos)

    base_row = {"model": "deepseek", "shape": "latent", "slots": live,
                "lists": len(owner), "live_pages": int(seen.any(2).sum()),
                "tokens_seen": tokens, "dtype": dtype.name}
    useful = tokens * c.latent_dim * dtype.itemsize
    flat_ops = 2.0 * H * (W + R) * fetched

    def row(variant, ms, **more):
        emit({**base_row, "variant": variant, "ms": round(ms, 4),
              "share_of_hbm_pct": round(100 * useful / (ms / 1e3) / HBM, 2),
              **more})

    fn = jax.jit(kernel)      # a list is scored whole: no ``--blocks``
    ms = _time(fn, (q, pool, base, *dev), args.calls)
    row("kernel, one pool", ms, flat_ops_share_of_peak_pct=round(
        100 * flat_ops / (ms / 1e3) / PEAK, 2))
    got = {"kernel": fn(q, pool, base, *dev)}
    lat = jax.jit(gather)(pool, base, dev[0])
    t_g = _time(jax.jit(gather), (pool, base, dev[0]), args.calls)
    t_a = _time(jax.jit(attend), (q, lat, *dev[1:]), args.calls)
    del lat
    row("xla gather (a layer)", t_g)
    row("xla attend (a layer)", t_a)
    row("xla gather + attend, one program",
        _time(jax.jit(xla), (q, pool, base, *dev), args.calls))
    got["xla"] = jax.jit(xla)(q, pool, base, *dev)
    if args.check:
        want = _latent_reference(c, q, pool, int(base), lists, owner, seen,
                                 scale)
        top = float(jnp.abs(want).max())
        for who, out in got.items():
            err = float(jnp.abs(out - want).max())
            emit({"model": "deepseek", "check": who, "max_abs_err": err,
                  "largest_value": top, "ok": err <= 2e-2 * top})
            if err > 2e-2 * top:
                raise SystemExit(f"deepseek: {who} is {err} from plain "
                                 f"float32 attention (largest {top})")


def _latent_reference(c, q, pool, base, lists, owner, seen, scale):
    """Plain float32 attention of every slot's 128 absorbed queries over
    the latent rows it sees, a slot at a time on the device: (B, H, R)."""
    high = jax.lax.Precision.HIGHEST

    @jax.jit
    def slot(qb, rows, ok):
        rows = rows.reshape(-1, rows.shape[-1]).astype(jnp.float32)
        s = jnp.einsum("hw,tw->ht", qb.astype(jnp.float32), rows,
                       precision=high) * scale
        p = jax.nn.softmax(jnp.where(ok[None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("ht,tr->hr", p, rows[:, :c.kv_lora_rank],
                          precision=high)

    out = []
    for b in range(q.shape[0]):
        mine = np.nonzero(owner == b)[0]
        if not len(mine):
            out.append(jnp.zeros((q.shape[1], c.kv_lora_rank), jnp.float32))
            continue
        out.append(slot(q[b], pool[base + lists[mine].reshape(-1)],
                        jnp.asarray(seen[mine].reshape(-1))))
    return jnp.stack(out)


PEAK = 197e12
# (model, variant, heads, key heads, D, Dv, window, sink, queries,
#  [(prefix, keys handed in, their first position)])
CHUNK_CASES = (
    ("cohere2", "full", 128, 8, 128, 128, None, False, 2048,
     [(0, 4096, 0), (8192, 16384, 0), (30720, 32768, 0),
      (0, 2048, 0), (8192, 10240, 0)]),        # the last two: live keys only
    ("cohere2", "window", 128, 8, 128, 128, 4096, False, 2048,
     [(8192, 6208, 4096), (30720, 6208, 26624)]),
    ("mimo", "full", 64, 4, 192, 128, None, False, 2048,
     [(0, 4096, 0), (8192, 16384, 0), (30720, 32768, 0), (8192, 10240, 0)]),
    ("mimo", "window", 64, 8, 192, 128, 128, True, 2048,
     [(8192, 2240, 8064)]),
    ("phi4flash", "window", 40, 20, 64, 128, 512, False, 512,
     [(1024, 1088, 512)]),
)


def _load_chunk_module(path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chunk_attention_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _chunk_reference(q, k, v, prefix, k0, scale, window, sink):
    """Plain float32 attention, a key head and 256 queries at a time on the
    device: (1, H, S, Dv) float32."""
    H, S = q.shape[1], q.shape[2]
    KV, C = k.shape[1], k.shape[2]
    g = H // KV
    high = jax.lax.Precision.HIGHEST

    @jax.jit
    def slab(qs, kk, vv, rows, sk):
        s = jnp.einsum("gqd,cd->gqc", qs.astype(jnp.float32),
                       kk.astype(jnp.float32), precision=high) * scale
        cols = k0 + jnp.arange(C)[None, :]
        seen = rows[:, None] >= cols
        if window is not None:
            seen &= rows[:, None] - cols < window
        s = jnp.where(seen[None], s, -jnp.inf)
        m = jnp.maximum(s.max(-1), sk[:, None])
        p = jnp.exp(s - m[..., None])
        den = p.sum(-1) + jnp.exp(sk[:, None] - m)
        return jnp.einsum("gqc,cd->gqd", p, vv.astype(jnp.float32),
                          precision=high) / den[..., None]

    sk = (sink if sink is not None
          else jnp.full((H,), -jnp.inf, jnp.float32)).reshape(KV, g)
    out = []
    step = min(S, 256)
    for h in range(KV):
        out.append(jnp.concatenate([
            slab(q[0, h * g:(h + 1) * g, a:a + step], k[0, h], v[0, h],
                 prefix + jnp.arange(a, a + step), sk[h])
            for a in range(0, S, step)], axis=1))
    return jnp.concatenate(out)[None]


def _live_pairs(S, C, prefix, k0, window):
    rows = prefix + np.arange(S, dtype=np.int64)
    hi = np.minimum(rows, k0 + C - 1)
    lo = np.full_like(rows, k0) if window is None \
        else np.maximum(rows - window + 1, k0)
    return int(np.maximum(hi - lo + 1, 0).sum())


def chunk(args, emit):
    """The prefill chunk's kernel alone (module docstring)."""
    import math

    from ray_tpu.ops import chunk_attention as tree

    dtype = jnp.dtype(args.dtype)
    mods = [("tree", tree)]
    for item in args.against:
        name, _, path = item.rpartition("=")
        mods.append((name or "against", _load_chunk_module(path)))
    # "" is what the module chooses from the shape.
    sweeps = [(t, g) for t in args.tiles.split(",")
              for g in args.group.split(",")]
    chosen = tree.tiles, tree.heads_a_step
    keys = jax.random.split(jax.random.key(0), 4)
    for (model, variant, H, KV, D, dv, window, has_sink, S,
         places) in CHUNK_CASES:
        if args.model not in ("all", model):
            continue
        scale = D ** -0.5
        q = jax.random.normal(keys[0], (1, H, S, D), jnp.float32).astype(dtype)
        sink = jax.random.normal(keys[3], (H,), jnp.float32) \
            if has_sink else None
        for prefix, C, k0 in places:
            if args.prefix is not None and prefix != args.prefix:
                continue
            k = jax.random.normal(keys[1], (1, KV, C, D),
                                  jnp.float32).astype(dtype)
            v = jax.random.normal(keys[2], (1, KV, C, dv),
                                  jnp.float32).astype(dtype)
            qo = jnp.asarray([prefix], jnp.int32)
            ko = jnp.asarray([k0], jnp.int32)
            pairs = _live_pairs(S, C, prefix, k0, window)
            ops = 2.0 * pairs * H * (D + dv)
            want = None
            if args.check:
                want = _chunk_reference(q, k, v, prefix, k0, scale, window,
                                        sink)
            for name, mod in mods:
                for t, g in (sweeps if mod is tree else [("", "")]):
                    tree.tiles, tree.heads_a_step = chosen
                    if t:
                        bq, bk = (int(x) for x in t.split("x"))
                        tree.tiles = lambda queries, window, bq=bq, bk=bk: (
                            math.gcd(queries, bq), bk)
                    if g:
                        tree.heads_a_step = \
                            lambda groups, *a, g=int(g): math.gcd(groups, g)
                    fn = jax.jit(lambda q, k, v, qo, ko, s, mod=mod:
                                 mod.chunk_attention(q, k, v, qo, ko, scale,
                                                     window, s))
                    row = {"chunk": model, "variant": variant,
                           "module": name, "heads": H, "kv_heads": KV,
                           "queries": S, "keys": C, "prefix": prefix,
                           "k_offset": k0, "live_pairs": pairs,
                           "tiles": t or "chosen", "group": g or "chosen",
                           "dtype": dtype.name}
                    try:
                        ms = _time(fn, (q, k, v, qo, ko, sink), args.calls)
                    except Exception as e:   # a sweep point Mosaic refuses
                        emit({**row, "error": str(e).splitlines()[0][:300]})
                        continue
                    emit({**row, "ms": round(ms, 4),
                          "share_of_peak_pct": round(
                              100 * ops / (ms / 1e3) / PEAK, 2)})
                    if want is not None:
                        got = fn(q, k, v, qo, ko, sink).astype(jnp.float32)
                        err = float(jnp.abs(got - want).max())
                        top = float(jnp.abs(want).max())
                        emit({**row, "check": name, "max_abs_err": err,
                              "largest_value": top, "ok": err <= 2e-2 * top})
                        if err > 2e-2 * top and not args.keep_going:
                            raise SystemExit(
                                f"{model} {variant} prefix {prefix}: {name} "
                                f"is {err} from plain float32 attention")
    tree.tiles, tree.heads_a_step = chosen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="phi4flash",
                    choices=("phi4flash", "cohere2", "deepseek", "mimo",
                             "all"))
    ap.add_argument("--chunk", action="store_true",
                    help="time ops/chunk_attention.py, the prefill's kernel")
    ap.add_argument("--against", action="append", default=[],
                    metavar="NAME=PATH", help="--chunk: another copy of "
                    "chunk_attention.py to time beside the tree's")
    ap.add_argument("--tiles", default="", help="--chunk: QxK,... to sweep")
    ap.add_argument("--group", default="",
                    help="--chunk: query heads a grid step, e.g. 1,2,4")
    ap.add_argument("--prefix", type=int, default=None,
                    help="--chunk: only the places at this prefix")
    ap.add_argument("--keep-going", action="store_true",
                    help="--chunk --check: report a failed check and go on "
                    "(an ablated copy is wrong by design)")
    ap.add_argument("--blocks", default="",
                    help="BLOCK_PAGES to time, e.g. 4,8,16")
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--partials", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:
        args.out = "chiprun_out/%s_sweep.jsonl" % (
            "chunk" if args.chunk else "paged")
    if jax.default_backend() != "tpu":
        raise SystemExit("microbench_paged.py times the chip's kernel: "
                         "run it through chiprun")
    c = phi4flash.Phi4FlashConfig()
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 5)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    blocks = [int(b) for b in args.blocks.split(",") if b] \
        or [pda.BLOCK_PAGES]

    def emit(row):
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    if args.chunk:
        chunk(args, emit)
        return
    if args.model not in ("phi4flash", "cohere2", "deepseek"):
        raise SystemExit("--model mimo / all go with --chunk")
    if args.model == "deepseek":
        deepseek(args, emit)
        return
    if args.model == "cohere2":
        for b in blocks:
            pda.BLOCK_PAGES = b
            cohere2(args, emit)
        return
    for shape, window, pool_pages, case in (
            ("window", c.window, 8 * WINDOW_POOL, _window_case(c, rng)),
            ("shared", None, SHARED_POOL, _shared_case(rng))):
        lists, owner, index, pos = case
        seen = _seen(owner, index, pos, window)
        n = len(owner)
        base = {"shape": shape, "lists": n, "pages_a_list": lists.shape[1],
                "live_lists": int(seen.any((1, 2)).sum()),
                "live_pages": int(seen.any(2).sum()),
                "padded_pages": int((~seen.any(2))[seen.any((1, 2))].sum()),
                "tokens_seen": int(seen.sum()), "dtype": dtype.name}
        useful = base["tokens_seen"] * 2 * c.kv_width * dtype.itemsize
        q = jax.random.normal(keys[0], (SLOTS, c.dim),
                              jnp.float32).astype(dtype)
        k = _pool(keys[1], pool_pages, c, dtype)
        v = _pool(keys[2], pool_pages, c, dtype)
        dev = [jnp.asarray(a) for a in (lists, owner, index, pos)]

        def row(variant, ms, **more):
            emit({**base, "variant": variant, "ms": round(ms, 4),
                  "share_of_hbm_pct": round(
                      100 * useful / (ms / 1e3) / HBM, 2), **more})

        got = {}
        for b in blocks:
            pda.BLOCK_PAGES = b
            fn = _kernel(c, window)
            row("kernel", _time(fn, (q, k, v, *dev), args.calls),
                block_pages=b)
            got[f"kernel block_pages={b}"] = fn(q, k, v, *dev)
            if args.partials:
                fn = _kernel_partials(c, window)
                row("kernel, partials a list",
                    _time(fn, (q, k, v, *dev), args.calls), block_pages=b)
                got[f"partials block_pages={b}"] = fn(q, k, v, *dev)
        if shape == "window":
            fn = _xla_window(c)
            row("xla gather + attend",
                _time(fn, (q, k, v, *dev), args.calls))
            got["xla"] = fn(q, k, v, *dev)
        else:
            gather, attend = _xla_shared(c)
            both = gather(k, v, dev[0], dev[1])
            t_g = _time(gather, (k, v, dev[0], dev[1]), args.calls)
            t_a = _time(attend, (q, *both, *dev[1:]), args.calls)
            row("xla gather (once a step)", t_g)
            row("xla attend (a layer)", t_a)
            row("xla a layer of eight", t_a + t_g / 8)
            got["xla"] = attend(q, *both, *dev[1:])
        if args.check:
            want = _reference(c, q, k, v, lists, owner, seen)
            top = float(jnp.abs(want).max())
            for who, out in got.items():
                err = float(jnp.abs(out - want).max())
                emit({"shape": shape, "check": who, "max_abs_err": err,
                      "largest_value": top, "ok": err <= 2e-2 * top})
                if err > 2e-2 * top:
                    raise SystemExit(f"{shape}: {who} is {err} from plain "
                                     f"float32 attention (largest {top})")


if __name__ == "__main__":
    main()
