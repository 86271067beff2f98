"""The state-space duality of a Mamba-2 layer (arXiv:2405.21060), with a
state carried in and out. A head ``h`` of ``P`` channels reads group ``h //
(H / G)`` of ``B`` and ``C`` (``N`` numbers each) and keeps a MATRIX:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T            (P x N, float32)
    y_t = S_t C_t + D x_t

with ONE decay a head (``A`` is (H,)), where Mamba-1 (``selective_scan.py``)
has one a channel and a state of 16 numbers. The state is laid out ``(H, P,
N)``: the ``N`` = 128 of ``ssm_state_size`` on lanes, the head's ``P`` = 64
on sublanes, whole float32 tiles with no padding; 4.19 MB a slot a layer at
the served widths, which a scan over time would move once a position.

* ``ssd_chunk``: a prefill chunk as matmuls. Sub-chunks of ``SUB_CHUNK`` =
  128 positions (the published ``chunk_size``); with ``a_t`` the running sum
  of ``dt A`` inside one,

      Y = ((C B^T) o L)(dt x) + exp(a) C S_in,   L[t, i] = exp(a_t - a_i), i <= t
      S_out = exp(a_last) S_in + sum_i exp(a_last - a_i) dt_i x_i B_i^T

  and the state goes from one sub-chunk to the next. The operands of every
  matmul are in ``x``'s dtype (three roundings: the mask times ``C B^T``,
  ``x`` times what is left of its decay, the state before it is read);
  decays, running sums, state and accumulation float32. A Pallas kernel
  over ``(row, group, sub-chunk)``, the sub-chunks in order: the tensors are
  read through BlockSpecs as the layer leaves them (heads flat on the
  lanes), the group's ``C B^T`` is formed once a grid step, a head's mask
  (64 KB) lives and dies in VMEM, the group's state (16 heads, 0.5 MB) stays
  in VMEM scratch, transposed (channels on the lanes), from the row's first
  sub-chunk to its last, and ``y`` is written once where it belongs. Two heads of 64 share a product's 128
  lanes: the state's read and its update are one full product a pair, a
  head's masked product runs on the pair's inputs and keeps its own lanes.
  A sub-chunk wholly past a row's ``lengths`` is skipped: nothing is
  fetched, the state passes, its rows of ``y`` are zeros. (XLA's form, a
  ``lax.scan`` that hands the mask and a stacked ``y`` through HBM, lives on
  as ``tests/test_ssd.py::chunk_jnp``.)
* ``ssd_step``: the decode's one token a stepping slot, a Pallas kernel
  over ``(slot, block of heads)`` that fetches a state tile once, scales
  it, adds ``dt x B^T``, reads it by ``C`` while it is in VMEM and writes it
  back where it lay (``input_output_aliases``). A slot outside the step is
  not read and not written: its grid steps name one block of the scratch
  row and copy it. What a head needs as a COLUMN (its ``P`` channels down
  the sublanes, the same in every lane) comes off the MXU: the slot's
  ``x^T`` (P, H) times a one-hot row selector, exact in one pass. (XLA's
  fusion of the same expression lives on as ``tests/test_ssd.py::step_jnp``,
  what the kernel is held to and ``microbench_ssd.py`` times it against.)

A position that is padding has ``dt`` 0: ``exp(0) = 1`` and ``0 x B`` add
nothing, so the state passes it bit for bit (``lengths`` masks ``dt``)."""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB_CHUNK = 128     # positions the masked product covers at once
CHUNK = "ssd_chunk"
STEP = "ssd_step"
UNROLL = 4          # heads of the step kernel's loop written out at once
# Bytes of one state block the kernel holds (in and out, twice each).
_BLOCK_BYTES = 2 << 20
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernel runs in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ a chunk


def _running_sum(v):
    """``cumsum`` down the sublanes of ``v`` (C, H) float32: log2(C) rolls,
    each added where it has not wrapped."""
    C = v.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    zero = jnp.zeros_like(v)
    d = 1
    while d < C:
        v = v + jax.lax.select(row >= d, pltpu.roll(v, d, 0), zero)
        d *= 2
    return v


def _chunk_kernel(n_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref,
                  y_ref, s_out, S, *, sub: int, per_group: int,
                  head_dim: int, pack: int):
    b, g, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    C, K, P = sub, per_group, head_dim
    W = pack * P                        # lanes of one product: ``pack`` heads
    H = dt_ref.shape[1]
    f32 = jnp.float32
    n = n_ref[b]

    # The group's state, TRANSPOSED (N, K P): a head's channels on the lanes
    # as ``x``'s and ``y``'s are, so its read and its update are plain
    # products and its decay a row.
    @pl.when(s == 0)
    def _():
        S[...] = s_ref[...].T

    # A sub-chunk wholly past the row's last real position: the state
    # passes, its rows of ``y`` are zeros.
    @pl.when(s * C >= n)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(s * C < n)
    def _():
        dtype = x_ref.dtype
        exact = (jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)

        def dot(p, q, contract):
            return jax.lax.dot_general(p, q, ((contract, ((), ()))),
                                       precision=exact,
                                       preferred_element_type=f32)

        def to_front(v):
            """The group's heads to lanes 0 .. K - 1 of ``v`` (.., H),
            where a static index finds them."""
            return pltpu.roll(v, jax.lax.rem(H - g * K, H), 1)

        pos = s * C + jax.lax.broadcasted_iota(jnp.int32, (C, H), 0)
        dt = dt_ref[...]
        dt = to_front(jax.lax.select(pos < n, dt, jnp.zeros_like(dt)))
        a = _running_sum(dt * to_front(a_ref[...]))             # (C, H)
        ea = jnp.exp(a)
        left = jnp.exp(a[C - 1:C] - a) * dt
        aT, dtT = a.T, dt.T                                     # (H, C)
        bm, cm = b_ref[...], c_ref[...]                         # (C, N)
        bmT = bm.astype(f32).T.astype(dtype)                    # (N, C)
        cb = dot(cm, bm, ((1,), (1,)))                          # (t, i)
        causal = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
        never = jnp.full((C, C), -jnp.inf, f32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (C, W), 1)

        def of_head(tiles):
            """(C, W): ``tiles[u]`` on the lanes of head ``u`` of the
            pack."""
            out = tiles[0]
            for u, tile in enumerate(tiles[1:], 1):
                out = jax.lax.select(lane >= u * P, tile, out)
            return out

        def column(v, k):
            return jnp.broadcast_to(v[:, k:k + 1], (C, W))

        def masked(k, xp):
            """Head ``k`` inside the sub-chunk: its mask over the group's
            ``C B^T``, rounded, times the pack's inputs."""
            decay = jnp.exp(jax.lax.select(
                causal, a[:, k:k + 1] - aT[k:k + 1, :], never))
            w = cb * (decay * dtT[k:k + 1, :])
            return dot(w.astype(dtype), xp, ((1,), (0,)))

        # The packs written out one after another (as the step kernel's
        # heads are, ``UNROLL``): every lane index is static, and a pack's
        # products run under the next one's masks. As a ``fori_loop`` of one
        # pack an iteration, its decays rolled to lane 0, a 2,048-token
        # chunk took 0.58 ms a layer; written out 0.30 (PR 58).
        for j in range(K // pack):
            heads = range(j * pack, (j + 1) * pack)
            lanes = slice(j * W, (j + 1) * W)
            xp = x_ref[:, lanes]                                # (C, W)
            # A head keeps its own lanes of the product on the pack's.
            y = of_head([masked(k, xp) for k in heads])
            # What came before it: the state, decayed up to each position.
            Sp = S[:, lanes]                                    # (N, W)
            decayed = of_head([column(ea, k) for k in heads])
            y = y + decayed * dot(cm, Sp.astype(dtype), ((1,), (0,)))
            y_ref[:, lanes] = y + d_ref[:, lanes] * xp.astype(f32)
            # The state after it: each position decayed to the end.
            xl = xp.astype(f32) * of_head([column(left, k) for k in heads])
            S[:, lanes] = decayed[C - 1:C] * Sp \
                + dot(bmT, xl.astype(dtype), ((1,), (0,)))

    @pl.when(s == pl.num_programs(2) - 1)
    def _():
        s_out[...] = S[...].T


@functools.partial(jax.jit, static_argnames=("sub_chunk",))
def ssd_chunk(x, dt, A, Bm, Cm, D, state, lengths=None,
              sub_chunk: int = SUB_CHUNK) -> Tuple[jax.Array, jax.Array]:
    """``T`` positions a row from the state before the first. ``x`` (B, T,
    H, P); ``dt`` (B, T, H) float32, after its softplus; ``A`` (H,)
    negative; ``Bm``, ``Cm`` (B, T, G, N); ``D`` (H,); ``state`` (B, H, P,
    N) float32 (zeros for a row that starts at position 0); ``lengths``
    (B,) the real positions of each row: the rest leave the state as it is
    and their output is junk (zeros in a sub-chunk that holds none).
    Returns ``(y (B, T, H, P) float32, the state after each row's last
    real position)``."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K, C = H // G, sub_chunk
    pack = math.gcd(K, max(1, 128 // P))
    f32 = jnp.float32
    with jax.named_scope(CHUNK):
        n = jnp.full((B,), T, jnp.int32) if lengths is None \
            else jnp.minimum(lengths.astype(jnp.int32), T)
        # Tensors as they lie, heads and groups flat on the lanes.
        x, dt = x.reshape(B, T, H * P), dt.astype(f32)
        Bm, Cm = (m.astype(x.dtype).reshape(B, T, G * N) for m in (Bm, Cm))
        short = -T % C
        if short:
            x, dt, Bm, Cm = (jnp.pad(m, [(0, 0), (0, short), (0, 0)])
                             for m in (x, dt, Bm, Cm))

        def at(b, g, s, n):
            # A sub-chunk past the row's last names the last's blocks
            # again: nothing is fetched for it.
            last = jax.lax.div(jnp.maximum(n[b] - 1, 0), C)
            return b, jnp.minimum(s, last), g

        seq = pl.BlockSpec((None, C, K * P), at)
        group = pl.BlockSpec((None, C, N), at)
        heads = pl.BlockSpec((None, K * P, N), lambda b, g, s, n: (b, g, 0))
        y, state = pl.pallas_call(
            functools.partial(_chunk_kernel, sub=C, per_group=K, head_dim=P,
                              pack=pack),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, G, (T + short) // C),
                in_specs=[seq,
                          pl.BlockSpec((None, C, H),
                                       lambda b, g, s, n: at(b, 0, s, n)),
                          pl.BlockSpec((1, H), lambda b, g, s, n: (0, 0)),
                          group, group,
                          pl.BlockSpec((1, K * P),
                                       lambda b, g, s, n: (0, g)), heads],
                out_specs=[pl.BlockSpec((None, C, K * P),
                                        lambda b, g, s, n: (b, s, g)),
                           heads],
                scratch_shapes=[pltpu.VMEM((N, K * P), f32)]),
            out_shape=[jax.ShapeDtypeStruct((B, T + short, H * P), f32),
                       jax.ShapeDtypeStruct((B, H * P, N), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=_interpret(),
            name=CHUNK,
        )(n, x, dt, A.astype(f32)[None], Bm, Cm,
          jnp.repeat(D.astype(f32), P)[None],
          state.astype(f32).reshape(B, H * P, N))
        return (y[:, :T].reshape(B, T, H, P), state.reshape(B, H, P, N))


# -------------------------------------------------------------- one token


def heads_a_block(heads: int, head_dim: int, state: int,
                  budget: int = _BLOCK_BYTES) -> int:
    """Heads of one slot a grid step holds: the most that divide ``heads``
    within ``budget`` bytes of float32 state."""
    fits = max(budget // (head_dim * state * 4), 1)
    return max(b for b in range(1, heads + 1)
               if heads % b == 0 and b <= fits)


def _step_kernel(rows_ref, live_ref, s_ref, xt_ref, dec_ref, dtb_ref, c_ref,
                 s_out, y_out, *, block: int, per_group: int, unroll: int):
    del rows_ref
    b, j = pl.program_id(0), pl.program_id(1)
    live = live_ref[b] > 0

    @pl.when(j == 0)
    def _():
        y_out[...] = jnp.zeros_like(y_out)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]

    @pl.when(live)
    def _():
        xt = xt_ref[...]                                     # (P, H)
        heads = xt.shape[1]
        exact = (jax.lax.Precision.DEFAULT if xt.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
        pick = jax.lax.broadcasted_iota(jnp.int32, (heads, s_ref.shape[-1]),
                                        0)
        lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)

        def head(i, y):
            h = j * block + i
            # The head's channels as a column, the same in every lane: a
            # 0/1 selector copies exactly.
            col = jnp.dot(xt, (pick == h).astype(xt.dtype), precision=exact,
                          preferred_element_type=jnp.float32)   # (P, N)
            new = s_ref[i] * dec_ref[pl.ds(h, 1), :] \
                + col * dtb_ref[pl.ds(h, 1), :]
            s_out[i] = new
            out = jnp.sum(new * c_ref[pl.ds(h // per_group, 1), :], axis=1,
                          keepdims=True)                        # (P, 1)
            return jnp.where(lane == h, out, y)

        def heads_of(k, y):
            # ``unroll`` heads an iteration, written out (Pallas TPU's
            # ``fori_loop`` unrolls fully or not at all): one head after
            # another leaves the MXU, the lane reductions and the vector
            # units waiting on each other (39% of the HBM peak at the
            # served widths); four at once read 78% (PR 57).
            for u in range(unroll):
                y = head(k * unroll + u, y)
            return y

        y_out[...] = jax.lax.fori_loop(0, block // unroll, heads_of,
                                       y_out[...])


def ssd_step(x, dt, A, Bm, Cm, D, state, rows, live,
             block: Optional[int] = None, unroll: Optional[int] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """One token a slot. ``x`` (B, H, P); ``dt`` (B, H) float32, after its
    softplus; ``A`` (H,); ``Bm``, ``Cm`` (B, G, N); ``D`` (H,); ``state``
    (R, H, P, N) float32, the state LEAF with layers and slots on one axis,
    of which row ``rows[b]`` is slot ``b``'s and is read and written where
    it lies; ``live`` (B,) bool: a slot outside it keeps its state bit for
    bit and gets zeros (name the scratch row in ``rows`` for it). Returns
    ``(y (B, H, P) float32, state)``."""
    B, H, P = x.shape
    G, N = Bm.shape[1], Bm.shape[2]
    f32 = jnp.float32
    block = block or heads_a_block(H, P, N)
    unroll = unroll or (UNROLL if block % UNROLL == 0 else 1)
    with jax.named_scope(STEP):
        dt = dt.astype(f32)
        dec = jnp.broadcast_to(jnp.exp(dt * A.astype(f32))[..., None],
                               (B, H, N))
        dtb = dt[..., None] * jnp.repeat(Bm.astype(f32), H // G, axis=1)
        state_spec = pl.BlockSpec(
            (None, block, P, N),
            # A slot outside the step names ONE block of its row (the
            # scratch row) at every grid step of its own: fetched and
            # written back once.
            lambda b, j, rows, live: (rows[b], j * live[b], 0, 0))

        def slot(*shape):
            return pl.BlockSpec((None,) + shape, lambda b, j, *_: (b, 0, 0))

        state, yt = pl.pallas_call(
            functools.partial(_step_kernel, block=block, per_group=H // G,
                              unroll=unroll),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, H // block),
                in_specs=[state_spec, slot(P, H), slot(H, N), slot(H, N),
                          slot(G, N)],
                out_specs=[state_spec, slot(P, H)]),
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((B, P, H), f32)],
            # Operand 2 (behind the two prefetched scalars) is the state.
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=_interpret(),
            name=STEP,
        )(rows.astype(jnp.int32), live.astype(jnp.int32), state,
          x.transpose(0, 2, 1), dec, dtb, Cm.astype(f32))
        y = yt.transpose(0, 2, 1) + D.astype(f32)[None, :, None] \
            * x.astype(f32)
        return jnp.where(live[:, None, None], y, 0.0), state
