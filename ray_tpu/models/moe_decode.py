"""What the served models behind the engine's seam share (``deepseek_decode``,
``mimo_decode``, ``phi4flash_decode``), so that none uses another as a
library: the cast of a replica's weights, the decode step's view of a
slot's pages in whole groups and of a window kind's pages, and the counters
an expert layer adds to a step. Nothing here knows a model's config."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

VIEW_GROUP = 16   # pages of ONE slot that a decode step scores together
# What a ``paged_decode_step`` counts beside its logits, summed over the
# expert layers (the step log's ``launch`` slice carries them).
MOE_STEP_STATS = ("moe_pairs", "moe_experts_hit", "moe_max_load")


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
