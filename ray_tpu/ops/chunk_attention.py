"""Pallas TPU kernel for a CHUNK's attention over plain (not latent) paged
keys and values, as ``models/mimo_decode.py``, ``phi4flash_decode.py`` and
``cohere2_moe_decode.py`` prefill: grouped-query heads, keys wider than
values (192 against 128), queries at a traced offset, and two static
variants of one body:

* **full**: causal over every key; the tiles above a query tile's
  frontier are skipped and their fetch is not issued (the block index is
  clamped to the last live one), as in ``ops/latent_attention.py``;
* **window**: a query at position ``i`` sees the keys ``j`` with ``0 <= i -
  j < window``. The key axis of the grid is then RELATIVE: a query tile
  visits the ``ceil((block_q + window - 1) / block_k) + 1`` key tiles from
  its first live one on, and not every tile of the row's keys (at 2,048
  queries over 2,304 keys that is 3 steps a query tile where the absolute
  axis has 9, most of them dead).

**The schedule of tiles (PR 51).** A live tile is *interior* when every
query of its query tile sees every one of its keys (``_inside``: two
scalar comparisons on the prefetched offsets) and takes a body with no
iota, compare or select; only a tile an edge crosses (the diagonal, a
window's trailing edge) builds a mask, once for all the heads of the
program. A program takes ``heads_a_step`` query heads of ONE key head (a
divisor of the group under ``VMEM_BUDGET``, from the tile and head sizes
alone: 8 of the 16 of Command A+ and of MiMo's full layers at 512 x
1,024, all 8 of MiMo's window layers at 256 x 256, phi-4-mini-flash's 2)
and walks them in a loop over one fetched key
tile: grid steps, dead steps and the keys' traffic fall by that factor,
each head keeps its own ``m``, ``l`` and accumulator. ``m`` stands in
all 128 lanes of its column and ``l`` as a partial sum a lane, so the
only reduction across the lanes in a step is the row maximum's. Alone on
a v5e at Command A+'s chunk (2,048 queries, 128 heads over 8; PERF.md
section 5): full at a prefix of 8,192 12.15 -> 8.88 ms (52 -> 71% of the
bf16 peak by live pairs), window 6.83 -> 4.85 (41 -> 58%). What is left:
with no softmax at all the same schedule reads 7.76 ms; the exponential
costs nothing beside the matmuls, the scale 5%, and the row maximum's
reduction across the lanes, 64 a head and tile, ~1.5 ms of the 8.88 (a
branch that moves ``m`` only when a score outgrows it costs more than it
saves; keys on the sublanes would make it whole-register work and is the
next thing to try here and in ``ops/latent_attention.py``).

Both take a **sink**: one learned logit a head that joins the softmax's
denominator and takes no value, ``p_ij = exp(a_ij - m) / (exp(s_h - m) +
sum_j exp(a_ij - m))``. The running softmax starts from it (``m = s_h``,
``l = 1``, nothing accumulated); a head without one starts from ``-1e30``,
which the first live tile's correction wipes out (``l`` lies as 1 / 128
in each of its 128 lanes: ``_lane_sums``).

The keys of row ``b`` start at absolute position ``k_offsets[b]`` (a window
layer hands in the few pages round its chunk, not the sequence from 0) and
its queries at ``q_offsets[b]``; both are scalar-prefetched, so one
compiled program serves every prefix.

The two ``pallas_call`` names, ``chunk_attn_full`` and
``chunk_attn_window``, are a contract with the benchmark, which finds the
kernels in a device trace by their HLO instruction names
(``benchmarks/cohere2_moe_counts.py``, ``mimo_counts.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NO_SINK = -1e30
_LANE = 128
BLOCK_Q = 512
BLOCK_K = 1024
# The window variant's smallest tiles: under a window of 128 a tile of 256
# queries has 383 live keys.
WINDOW_BLOCK = 256
# What a program may keep in fast memory (of a v5e core's 128 MiB; past
# ``_VMEM_DEFAULT``, Mosaic's scoped limit, the call asks for it), and the
# bytes of temporaries a (query, key) pair of ONE head's tile is reckoned
# at.
VMEM_BUDGET = 24 << 20
_VMEM_DEFAULT = 16 << 20
_SCORE_BYTES = 16


def tiles(queries: int, window: Optional[int]) -> Tuple[int, int]:
    """``(block_q, block_k)`` for ``queries`` queries a row. The full
    variant takes the largest tiles; the window variant takes key tiles of
    about a quarter of the window, within ``WINDOW_BLOCK`` .. ``BLOCK_K``:
    a window of 128 or 512 keeps tiles of 256 x 256 (little of a larger
    tile would be live), a window of 4,096 the full variant's 512 x 1,024
    (6 steps a query tile, three quarters of them live; at 256 x 256 it
    would be 18 steps of a sixth of the work each, under the grid's cost a
    step). Swept again with several heads a step (PR 51): every smaller
    tile is slower at Command A+'s and MiMo's full shapes (256 x 1,024
    +24%, 512 x 512 +65%), so the answers stand."""
    if window is None:
        return math.gcd(queries, BLOCK_Q), BLOCK_K
    quarter = 1 << max(window // 4, 1).bit_length() - 1
    block_k = min(BLOCK_K, max(WINDOW_BLOCK, quarter))
    return math.gcd(queries, min(BLOCK_Q, block_k)), block_k


def _vmem_bytes(heads: int, block_q: int, block_k: int, d: int, dv: int,
                itemsize: int) -> int:
    """What a program of ``heads`` query heads keeps in fast memory: the
    q, out, K and V blocks twice (the pipeline's two buffers), the
    accumulator and the 128-lane ``m`` / ``l`` columns a head, and ONE
    head's (block_q, block_k) float32 temporaries (scores, probabilities,
    their rounded copy, an edge tile's mask), which the heads' loop
    reuses."""
    blocks = 2 * itemsize * (heads * block_q * (d + dv)
                             + block_k * (d + dv))
    scratch = 4 * heads * block_q * (dv + 2 * _LANE)
    return blocks + scratch + _SCORE_BYTES * block_q * block_k


def heads_a_step(groups: int, block_q: int, block_k: int, d: int, dv: int,
                 itemsize: int) -> int:
    """How many of the ``groups`` query heads that share a key head one
    program takes: the largest divisor of ``groups`` that fits
    ``VMEM_BUDGET``. From the shapes alone."""
    return max(g for g in range(1, groups + 1)
               if groups % g == 0 and (g == 1 or _vmem_bytes(
                   g, block_q, block_k, d, dv, itemsize) <= VMEM_BUDGET))


def _first_tile(q0, k0, window, block_k):
    """The first key tile a query tile starting at ``q0`` can see, of keys
    that start at position ``k0``."""
    if window is None:
        return 0
    return jnp.maximum(q0 - (window - 1) - k0, 0) // block_k


def _inside(q0, c0, block_q: int, block_k: int, window: Optional[int]):
    """Whether every query of the tile at ``q0`` sees every key of the
    tile at ``c0``: the last key at or before the first query and, under a
    window, the first key inside the LAST query's window. Such a tile
    needs no mask."""
    inside = c0 + block_k - 1 <= q0
    if window is not None:
        inside &= c0 > q0 + block_q - 1 - window
    return inside


def _lanes(col, width: int):
    """A (rows, 128) column whose lanes all hold the row's value, as wide
    as ``width``: whole copies where the lanes divide it."""
    if width % _LANE == 0:
        return pltpu.repeat(col, width // _LANE, 1)
    return jnp.broadcast_to(col[:, :1], (col.shape[0], width))


def _lane_sums(p):
    """(rows, 128): lane ``j`` holds the sum of ``p``'s columns ``j``, ``j
    + 128``, ...: whole-register adds, where a row's one sum would cross
    the lanes once a tile (a seventh of the kernel's time, PERF.md
    section 5). ``l`` is kept so, a partial sum a lane, and summed over
    the lanes once, at the end. A key tile is whole lanes wide."""
    out = p[:, :_LANE]
    for c in range(_LANE, p.shape[-1], _LANE):
        out = out + p[:, c:c + _LANE]
    return out


def _kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, sink_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, block_q, block_k, window,
            key_tiles):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    q0 = qoff_ref[b] + i * block_q
    k0 = koff_ref[b]
    tile = _first_tile(q0, k0, window, block_k) + j
    c0 = k0 + tile * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
        l_ref[...] = jnp.full_like(l_ref, 1.0 / _LANE)

    live = (tile < key_tiles) & (q0 + block_q - 1 >= c0)
    if window is not None:
        live &= c0 + block_k - 1 > q0 - window
    inside = _inside(q0, c0, block_q, block_k, window)

    def step(seen):
        """The tile's part of the running softmax of each of the program's
        heads in turn; ``seen`` is the tile's mask, one for all heads, or
        ``None`` for a tile that needs none. ``m`` stands in all 128 lanes
        of its column and ``l`` is a partial sum a lane (``_lane_sums``),
        so a head's update is whole loads and stores and one reduction
        across the lanes, the maximum's."""
        def head(h, carry):
            s = jax.lax.dot_general(q_ref[0, h], k_ref[0, 0],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if seen is not None:
                s = jnp.where(seen, s, NO_SINK)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, block_k))
            if seen is not None:
                # A row that has seen no key yet and has no sink stands at
                # ``NO_SINK``, where a masked score's exp would be 1.
                p = jnp.where(seen, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + _lane_sums(p)
            m_ref[h] = m_new
            v = v_ref[0, 0]
            acc_ref[h] = acc_ref[h] * _lanes(
                corr, v.shape[-1]) + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, q_ref.shape[1], head, 0)

    @pl.when(live & inside)
    def _interior():
        step(None)

    @pl.when(live & jnp.logical_not(inside))
    def _edge():
        rows = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = c0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        seen = rows >= cols
        if window is not None:
            seen &= rows - cols < window
        step(seen)

    @pl.when(j == pl.num_programs(3) - 1)
    def _final():
        # ``l`` is 1 or more where there is a sink and where no key was
        # seen; nothing divides by zero.
        o_ref[0] = (acc_ref[...] / jnp.sum(
            l_ref[...], axis=-1, keepdims=True)).astype(o_ref.dtype)


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernel runs in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


def _pad_to(x, axis: int, multiple: int):
    short = -x.shape[axis] % multiple
    if not short:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, short)
    return jnp.pad(x, pad)


def chunk_attention(q, k, v, q_offsets, k_offsets, scale: float,
                    window: Optional[int] = None,
                    sink: Optional[jax.Array] = None):
    """Attention of ``S`` queries a row over ``C`` keys.

    ``q`` (B, H, S, D), ``k`` (B, KV, C, D), ``v`` (B, KV, C, Dv), ``H`` a
    multiple of ``KV`` (head ``h`` reads key head ``h // (H / KV)``);
    ``q_offsets``, ``k_offsets`` (B,) int32: query ``i`` of row ``b`` sits
    at position ``q_offsets[b] + i``, key ``j`` at ``k_offsets[b] + j``. A
    query sees the keys at positions up to its own and, under ``window``,
    no further back than ``window - 1``. ``sink`` (H,) float32 joins each
    head's softmax denominator. Scores are ``q . k * scale``; softmax and
    accumulation in float32, probabilities rounded to ``v``'s dtype for
    the value product. Returns (B, H, S, Dv) in ``q``'s dtype. The keys are
    padded here to whole tiles and ``D`` to whole lanes (zeros, which a
    score does not see; a padded key lies past every query)."""
    B, H, S, _ = q.shape
    KV, dv = k.shape[1], v.shape[-1]
    groups = H // KV
    block_q, block_k = tiles(S, window)
    block_k = min(block_k, -(-k.shape[2] // _LANE) * _LANE)
    q, k = _pad_to(q, 3, _LANE), _pad_to(_pad_to(k, 3, _LANE), 2, block_k)
    v = _pad_to(v, 2, block_k)
    d, key_tiles = q.shape[-1], k.shape[2] // block_k
    heads = heads_a_step(groups, block_q, block_k, d, dv, q.dtype.itemsize)
    need = _vmem_bytes(heads, block_q, block_k, d, dv, q.dtype.itemsize)
    steps = key_tiles
    if window is not None:
        steps = min(key_tiles, -(-(block_q + window - 1) // block_k) + 1)
    if sink is None:
        sink = jnp.full((H,), NO_SINK, jnp.float32)
    sink = jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                            (H, 1, _LANE))

    def q_map(b, h, i, j, qoff, koff):
        return b, h, i, 0

    def k_map(b, h, i, j, qoff, koff):
        q0 = qoff[b] + i * block_q
        last = jnp.clip((q0 + block_q - 1 - koff[b]) // block_k, 0,
                        key_tiles - 1)
        tile = _first_tile(q0, koff[b], window, block_k) + j
        return b, h * heads // groups, jnp.minimum(tile, last), 0

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    kernel = functools.partial(_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, window=window,
                               key_tiles=key_tiles)
    name = "chunk_attn_full" if window is None else "chunk_attn_window"
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, H // heads, S // block_q, steps),
                in_specs=[
                    spec((1, heads, block_q, d), q_map),
                    spec((1, 1, block_k, d), k_map),
                    spec((1, 1, block_k, dv), k_map),
                    spec((heads, 1, _LANE),
                         lambda b, h, i, j, qoff, koff: (h, 0, 0)),
                ],
                out_specs=spec((1, heads, block_q, dv), q_map),
                scratch_shapes=[
                    pltpu.VMEM((heads, block_q, dv), jnp.float32),
                    pltpu.VMEM((heads, block_q, _LANE), jnp.float32),
                    pltpu.VMEM((heads, block_q, _LANE), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
                vmem_limit_bytes=need if need > _VMEM_DEFAULT else None),
            interpret=_interpret(),
            name=name,
        )(q_offsets.astype(jnp.int32), k_offsets.astype(jnp.int32),
          q, k, v, sink)
