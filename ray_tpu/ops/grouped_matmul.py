"""Pallas TPU kernels for the held experts' matmuls over ROW TILES THAT
BELONG TO ONE EXPERT EACH, as ``ops/moe.py::held_experts_ffn`` lays a
chunk's held pairs out: tile ``t`` of the rows is ``tile_group[t]``'s, its
first rows live and the rest padding, and only the first ``live_tiles``
tiles hold anything.

``jax.lax.ragged_dot``'s kernel tiles 512 rows whatever a group holds, and a
held expert of a chunk has 64-160. Here the row tile is an argument (the
caller takes the power of two that holds a balanced expert's rows), and the
grid is ``(tiles, N / block_n)`` with the contraction whole: a step
multiplies a ``(block_m, K)`` tile, fetched once a tile, by one ``(K,
block_n)`` column block of its expert's matrix, so every step moves one
block of weights under one block's matmul and a balanced expert's matrix is
read once. (With the groups packed end to end and a tile shared by two
experts, as megablox lays them, the blocks come in bursts at each group's
end: 3.49 ms for a command-a-plus layer's three matmuls where this layout
takes 2.53, against 1.97 of weights; PR 50, ``microbench_moe.py``.)

``grouped_swiglu`` is the first two matmuls and the gate in one kernel (the
rows' tile is read once for both, and neither product goes to memory);
``add_rows`` is the way back: each live row, under its weight, added in
float32 into its token's row of a ``(tokens, D)`` result that stays in fast
memory a column block at a time. Dead tiles cost a grid step and no fetch
(their block indices are the last live tile's); a tile's padding rows are
computed and never added: ``add_rows`` SELECTS by the tile's live rows
before anything meets arithmetic with what is kept.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MATMUL = "moe_grouped_matmul"
SWIGLU = "moe_grouped_swiglu"
ADD_ROWS = "moe_add_rows"
_LANE = 128
# A column block is held twice (the pipeline's two buffers), the gate's and
# the up projection's side by side in ``grouped_swiglu``: 4 MiB each keeps a
# kernel's fast memory under 32 MiB at every served width. 8 MiB read 2-3%
# faster and 2 MiB 2-4% slower (PR 50).
_BLOCK_BYTES = 4 * 2 ** 20
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernels run in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


def block_lanes(rows: int, lanes: int, itemsize: int,
                budget: int = _BLOCK_BYTES) -> int:
    """The widest column block of a ``(rows, lanes)`` array, a whole number
    of 128 lanes that divides ``lanes``, within ``budget`` bytes (``lanes``
    itself where they are no whole number of 128: the debug sizes)."""
    if lanes % _LANE:
        return lanes
    fits = max(budget // (rows * itemsize), _LANE)
    return max(b for b in range(_LANE, lanes + 1, _LANE)
               if lanes % b == 0 and b <= fits)


def _last_live(t, live):
    """The tile a grid step reads: its own, or for a dead tile the last live
    one's, so that its block index repeats and nothing is fetched."""
    return jnp.minimum(t, jnp.maximum(live[0] - 1, 0))


def _matmul_kernel(tile_group, live, lhs_ref, rhs_ref, out_ref):
    del tile_group

    @pl.when(pl.program_id(0) < live[0])
    def _():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[...],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _swiglu_kernel(tile_group, live, lhs_ref, gate_ref, up_ref, out_ref):
    del tile_group

    @pl.when(pl.program_id(0) < live[0])
    def _():
        x = lhs_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        out_ref[...] = (jax.nn.silu(gate) * up).astype(out_ref.dtype)


def _over_tiles(kernel, name, lhs, rhss, tile_group, live_tiles, block_m):
    rows, k = lhs.shape
    n = rhss[0].shape[2]
    if rows % block_m:
        raise ValueError(f"{rows} rows are no whole number of tiles of "
                         f"{block_m}")
    block_n = block_lanes(k, n, rhss[0].dtype.itemsize)
    blocks = n // block_n

    def lhs_map(t, j, tile_group, live):
        return _last_live(t, live), 0

    def rhs_map(t, j, tile_group, live):
        # A dead tile keeps the block the last live step held.
        return (tile_group[_last_live(t, live)], 0,
                jnp.where(t < live[0], j, blocks - 1))

    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(rows // block_m, blocks),
                in_specs=[pl.BlockSpec((block_m, k), lhs_map)]
                + [pl.BlockSpec((None, k, block_n), rhs_map)] * len(rhss),
                out_specs=pl.BlockSpec(
                    (block_m, block_n),
                    lambda t, j, tile_group, live: (t, j))),
            out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=_interpret(),
            name=name,
        )(tile_group.astype(jnp.int32),
          jnp.reshape(live_tiles, (1,)).astype(jnp.int32), lhs, *rhss)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   live_tiles: jax.Array, block_m: int) -> jax.Array:
    """``lhs`` (rows, K) in tiles of ``block_m`` rows; ``rhs`` (G, K, N);
    ``tile_group`` (rows / block_m,) int32, the group of each tile;
    ``live_tiles`` () int32. Returns (rows, N) in ``lhs``'s dtype,
    accumulated in float32: tile ``t < live_tiles`` times
    ``rhs[tile_group[t]]``; the other tiles are not defined."""
    return _over_tiles(_matmul_kernel, MATMUL, lhs, (rhs,), tile_group,
                       live_tiles, block_m)


def grouped_swiglu(lhs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   tile_group: jax.Array, live_tiles: jax.Array,
                   block_m: int) -> jax.Array:
    """``silu(lhs @ w_gate[g]) * (lhs @ w_up[g])`` a tile, ``g`` the tile's
    group, both products and the gate in float32 and the result in
    ``lhs``'s dtype; arguments as ``grouped_matmul``'s."""
    return _over_tiles(_swiglu_kernel, SWIGLU, lhs, (w_gate, w_up),
                       tile_group, live_tiles, block_m)


def _add_rows_kernel(token, tile_rows, live, rows_ref, weight_ref, y_ref,
                     scaled_ref, *, block_m):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t < live[0])
    def _():
        held = tile_rows[t]
        row = jax.lax.broadcasted_iota(jnp.int32, scaled_ref.shape, 0)
        # Selected, not multiplied by a zero: a padding row may hold
        # anything.
        scaled_ref[...] = jnp.where(
            row < held,
            rows_ref[...].astype(jnp.float32) * weight_ref[...], 0.0)

        def add(r, carry):
            at = token[t * block_m + r]
            y_ref[pl.ds(at, 1), :] += scaled_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, held, add, 0)


def add_rows(rows: jax.Array, weights: jax.Array, token: jax.Array,
             tile_rows: jax.Array, live_tiles: jax.Array, tokens: int,
             block_m: int) -> jax.Array:
    """``y[token[r]] += weights[r] * rows[r]`` in float32 over the first
    ``tile_rows[t]`` rows of every tile ``t < live_tiles``: ``rows`` (R, D),
    ``weights`` (R,) float32, ``token`` (R,) int32 in ``[0, tokens)``,
    ``tile_rows`` (R / block_m,) int32. Returns ``y`` (tokens, D) float32,
    zero where no row went. A column block of ``y`` stays in fast memory
    while the tiles go by, and a row is added where it lies: no sort of the
    rows by token and no pass over them a duplicate (XLA's scatter-add took
    4.2 ms at DeepSeek-V2's 3,840 x 5,120, this 0.31; PR 50)."""
    count, d = rows.shape
    block_n = block_lanes(tokens, d, 4, 2 * _BLOCK_BYTES)
    tiles = count // block_m

    with jax.named_scope(ADD_ROWS):
        return pl.pallas_call(
            functools.partial(_add_rows_kernel, block_m=block_m),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(d // block_n, tiles),
                in_specs=[
                    pl.BlockSpec(
                        (block_m, block_n),
                        lambda j, t, *s: (_last_live(t, s[-1]), j)),
                    pl.BlockSpec(
                        (block_m, 1),
                        lambda j, t, *s: (_last_live(t, s[-1]), 0)),
                ],
                out_specs=pl.BlockSpec((tokens, block_n),
                                       lambda j, t, *s: (0, j)),
                scratch_shapes=[pltpu.VMEM((block_m, block_n),
                                           jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=_interpret(),
            name=ADD_ROWS,
        )(token.astype(jnp.int32), tile_rows.astype(jnp.int32),
          jnp.reshape(live_tiles, (1,)).astype(jnp.int32), rows,
          weights.reshape(-1, 1).astype(jnp.float32))
