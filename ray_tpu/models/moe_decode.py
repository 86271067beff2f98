"""What the served models behind the engine's seam share (``deepseek_decode``,
``mimo_decode``, ``phi4flash_decode``, ``cohere2_moe_decode``,
``nemotron_h_decode``; ``mimo``, ``cohere2_moe`` and ``nemotron_h`` for
their weights), so that none uses another as a
library: a replica's seeded weights made leaf by leaf and their cast, the
decode step's view of a slot's pages in whole groups and of a window
kind's pages, a decode step's queries laid flat over the cached lanes, the layer loop over segments of one kind with the kind's
pool in its carry, and the counters an expert layer adds to a step.
Nothing here knows a model's config."""

from __future__ import annotations

import math
import zlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VIEW_GROUP = 16   # pages of ONE slot that a decode step scores together
# What a ``paged_decode_step`` counts beside its logits, summed over the
# expert layers (the step log's ``launch`` slice carries them).
MOE_STEP_STATS = ("moe_pairs", "moe_experts_hit", "moe_max_load")


def is_spec(x) -> bool:
    """A leaf of a model's ``_shapes`` tree: ``(shape, how it is drawn)``."""
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def count_leaves(shapes) -> int:
    """The numbers in a tree of ``is_spec`` leaves."""
    return sum(math.prod(spec[0])
               for spec in jax.tree.leaves(shapes, is_leaf=is_spec))


def init_leaves(shapes, key: jax.Array, dtype,
                float32_std: Optional[Dict[str, float]] = None):
    """Seeded random weights for a tree (dicts and lists) of ``(shape,
    fan_in)`` leaves, made LEAF BY LEAF in ``dtype``, a stacked leaf
    (three axes or more) one layer at a time, so the float32 transient is
    one layer of one leaf (``deepseek.init_params``). ``fan_in`` a number
    draws ``N(0, 1 / fan_in)``; ``None`` is a norm scale (float32 ones); a
    name of ``float32_std`` draws float32 ``N(0, std^2)``. A leaf's key is
    folded from its path, so a tree's other leaves do not move it."""
    dtype = jnp.dtype(dtype)

    def leaf(path, spec):
        shape, fan_in = spec
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))
        if isinstance(fan_in, str):
            return jax.random.normal(k, shape, jnp.float32) \
                * float32_std[fan_in]
        scale = float(fan_in) ** -0.5

        def one(k, shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(dtype)

        if len(shape) < 3:
            return jax.jit(one, static_argnums=1)(k, shape)

        def fill(k):
            return jax.lax.fori_loop(
                0, shape[0],
                lambda i, buf: buf.at[i].set(
                    one(jax.random.fold_in(k, i), shape[1:])),
                jnp.zeros(shape, dtype))

        return jax.jit(fill)(k)

    def walk(tree, prefix):
        if isinstance(tree, list):
            return [walk(sub, f"{prefix}{i}/") for i, sub in enumerate(tree)]
        return {name: (walk(sub, prefix + name + "/")
                       if isinstance(sub, (dict, list))
                       else leaf(prefix + name, sub))
                for name, sub in tree.items()}

    return walk(shapes, "")


def cast_weights(params: Dict[str, Any], dtype,
                 float32_leaves: Tuple[str, ...],
                 donate: bool = False) -> Dict[str, Any]:
    """``params`` with every leaf in ``dtype`` but those named
    ``float32_leaves``, which stay as they are. A leaf already in that
    dtype is passed through; ``donate`` deletes a converted leaf's source
    as soon as its copy exists, so the transient is one leaf."""
    dtype = jnp.dtype(dtype)

    def held(path, w):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in float32_leaves or w.dtype == dtype:
            return w
        out = jnp.asarray(w, dtype=dtype)
        if donate:
            out.block_until_ready()
            w.delete()
        return out

    return jax.tree_util.tree_map_with_path(held, params)


def moe_step_stats(sizes: jax.Array) -> jax.Array:
    """One expert layer's ``MOE_STEP_STATS`` from its ``sizes``."""
    return jnp.stack([sizes.sum(), (sizes > 0).sum(),
                      sizes.max()]).astype(jnp.float32)


def view_rows(counts) -> int:
    """Rows ``live_page_view`` needs for these page counts: each slot's
    pages rounded up to whole groups. The engine picks the rung from
    it."""
    counts = np.asarray(counts)
    return int((-(-counts // VIEW_GROUP) * VIEW_GROUP).sum())


def live_page_view(block_tables, counts, rows: int):
    """``llama_decode.live_page_view`` with every slot's rows padded up to
    a multiple of ``VIEW_GROUP``: ``(3, rows)`` int32, a row ``(pool page,
    owning slot, index of the page in the slot's sequence)``. A row that
    pads a slot's last group is the scratch page under the slot's own
    name at an index past its pages, so the position mask hides it whole;
    the rows past the list are the scratch page, owned by slot -1. Every
    aligned group of ``VIEW_GROUP`` rows so has ONE owner, and the decode
    step can score a group against one slot's queries and add it up as
    one matmul, with no per-row partial output."""
    tables = np.asarray(block_tables)
    counts = np.asarray(counts)
    padded = -(-counts // VIEW_GROUP) * VIEW_GROUP
    width = max(int(padded.max(initial=0)), 1)
    slot, index = np.nonzero(np.arange(width)[None, :] < padded[:, None])
    n = len(slot)
    if n > rows:
        raise ValueError(f"{n} rows of live pages do not fit a view of "
                         f"{rows}")
    view = np.zeros((3, rows), np.int32)
    view[1] = -1
    real = index < counts[slot]
    view[0, :n] = np.where(
        real, tables[slot, np.minimum(index, tables.shape[1] - 1)], 0)
    view[1, :n] = slot
    view[2, :n] = index
    return view


def window_page_view(table, first, held, rows: int):
    """A window kind's part of a decode step's view: ``(2, slots, rows)``
    int32, for each slot the pool pages of its last ``rows`` window pages
    and their indices in the sequence, the slot holding ``held`` pages
    from index ``first`` on; a slot that does not step (0 held) and the
    entries past a slot's pages are the scratch page at index -1, which no
    position matches."""
    table = np.asarray(table)
    first, held = np.asarray(first), np.asarray(held)
    end = first + held
    index = np.maximum(first, end - rows)[:, None] \
        + np.arange(rows)[None, :]
    real = index < end[:, None]
    window = np.zeros((2,) + index.shape, np.int32)
    window[0] = np.where(real, np.take_along_axis(
        table, np.minimum(index, table.shape[1] - 1), axis=1), 0)
    window[1] = np.where(real, index, -1)
    return window


def kinds_page_view(block_tables: Dict[str, Any], counts: Dict[str, Any],
                    rows: Dict[str, int], full: str = "full",
                    window: str = "window") -> Dict[str, np.ndarray]:
    """The decode step's view of a pool of two kinds, built on the host.

    ``full``: ``live_page_view`` of the full kind's tables and counts on
    ``rows[full]`` rows (the engine's ladder): a slot's pages in whole
    groups of ``VIEW_GROUP``.

    ``window``: ``window_page_view``, ``(2, slots, rows[window])`` int32,
    ``counts[window]`` being ``(first held index, pages held)`` a slot."""
    return {full: live_page_view(block_tables[full], counts[full],
                                 rows[full]),
            window: window_page_view(block_tables[window], *counts[window],
                                     rows[window])}


def flat_queries(q, kv_heads: int, heads: int, head_dim: int):
    """``q`` (B, H, D) -> (B, H, KV x D): each head's query laid out over
    ALL key heads' lanes, zero but on its own, so that a decode step's
    score is one matmul against the keys as they are cached, flat
    (``ops/paged_decode_attention.py``: KV times the operations, on rows
    that are nothing; a per-head view of the pages would be a transposed
    copy)."""
    B = q.shape[0]
    own = jnp.eye(kv_heads, dtype=q.dtype)
    flat = jnp.einsum("bkhd,kj->bkhjd",
                      q.reshape(B, kv_heads, heads // kv_heads, head_dim),
                      own)
    return flat.reshape(B, heads, kv_heads * head_dim)


def own_values(part, kv_heads: int, heads: int, head_dim: int):
    """(B, H, KV x D) weighted values over all lanes -> (B, H, D): each
    head's own key head's block."""
    B = part.shape[0]
    part = part.reshape(B, kv_heads, heads // kv_heads, kv_heads, head_dim)
    own = jnp.einsum("bkhjd,kj->bkhd", part,
                     jnp.eye(kv_heads, dtype=part.dtype),
                     precision=jax.lax.Precision.HIGHEST)
    return own.reshape(B, heads, head_dim)


def scan_segments(body: Callable, x, segments: Sequence[Any],
                  leaves: Sequence[Dict[str, Any]],
                  pool: Dict[str, jax.Array]):
    """One ``scan`` a segment (consecutive layers of one kind of page,
    stacked) with the pool of the segment's kind in its CARRY, flat
    (``llama_decode._scan_layers``): ``body(seg, x, k_pool, v_pool, layer,
    base) -> (x, k_pool, v_pool, stats)``, page ``p`` of the layer at row
    ``base + p`` of the kind's leaves ``<kind>_k`` / ``<kind>_v``. A
    segment is ``(kind, layers, first)`` by name, ``first`` its first
    layer's index among the layers of its kind; one without experts says
    ``moe`` False. A segment may name the two leaves it carries itself
    (``carries``: a recurrent kind's state leaves, ``[layers, slots + 1,
    ...]``, row ``base + slot``), or none, ``()``, and is then handed
    ``None`` for both. Returns ``(x, pool, stats)``, the expert layers'
    ``MOE_STEP_STATS`` summed."""
    shapes = {name: leaf.shape for name, leaf in pool.items()}
    flat = {name: leaf.reshape((leaf.shape[0] * leaf.shape[1],)
                               + leaf.shape[2:])
            for name, leaf in pool.items()}
    stats = jnp.zeros((len(MOE_STEP_STATS),), jnp.float32)
    for seg, stacked in zip(segments, leaves):
        names = getattr(seg, "carries",
                        (f"{seg.kind}_k", f"{seg.kind}_v"))
        bases = (seg.first + jnp.arange(seg.layers, dtype=jnp.int32)) \
            * (shapes[names[0]][1] if names else 0)
        # The experts do not ride the scan's ``xs``: a layer of them
        # sliced out for the grouped matmul would be a copy
        # (``ops.moe.held_experts_ffn``); the stack goes in whole.
        rest = {k: v for k, v in stacked.items() if k != "experts"}

        def step(carry, inp, seg=seg, stacked=stacked):
            x, k_pool, v_pool, stats = carry
            layer, base, at = inp
            if getattr(seg, "moe", True):
                layer = {**layer, "experts": stacked["experts"],
                         "expert_layer": at}
            x, k_pool, v_pool, more = body(seg, x, k_pool, v_pool, layer,
                                           base)
            return (x, k_pool, v_pool, stats + more), None

        carried = tuple(flat[n] for n in names) or (None, None)
        (x, *carried, stats), _ = jax.lax.scan(
            step, (x, *carried, stats),
            (rest, bases, jnp.arange(seg.layers, dtype=jnp.int32)))
        flat.update(zip(names, carried))
    return x, {name: flat[name].reshape(shapes[name])
               for name in pool}, stats
