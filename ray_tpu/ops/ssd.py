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
  matmul are in ``x``'s dtype; decays, state and accumulation float32.
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


def ssd_chunk(x, dt, A, Bm, Cm, D, state, lengths=None,
              sub_chunk: int = SUB_CHUNK) -> Tuple[jax.Array, jax.Array]:
    """``T`` positions a row from the state before the first. ``x`` (B, T,
    H, P); ``dt`` (B, T, H) float32, after its softplus; ``A`` (H,)
    negative; ``Bm``, ``Cm`` (B, T, G, N); ``D`` (H,); ``state`` (B, H, P,
    N) float32 (zeros for a row that starts at position 0); ``lengths``
    (B,) the real positions of each row: the rest leave the state as it is
    and their output is junk. Returns ``(y (B, T, H, P) float32, the state
    after each row's last real position)``."""
    with jax.named_scope(CHUNK):
        return _chunk(x, dt, A, Bm, Cm, D, state, lengths, sub_chunk)


def _chunk(x, dt, A, Bm, Cm, D, state, lengths, sub_chunk):
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K = H // G
    f32 = jnp.float32
    dt = dt.astype(f32)
    if lengths is not None:
        dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    C = min(sub_chunk, T)
    short = -T % C
    if short:
        x, dt, Bm, Cm = (jnp.pad(a, [(0, 0), (0, short)]
                                 + [(0, 0)] * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    causal = jnp.tril(jnp.ones((C, C), bool))
    A = A.astype(f32)

    def cut(a):
        """(B, T, ...) -> (T / C, B, C, ...), sub-chunks leading."""
        return jnp.moveaxis(a.reshape((B, -1, C) + a.shape[2:]), 1, 0)

    def body(S, inp):
        xc, dtc, bc, cc = inp                     # (B, C, ...)
        a = jnp.cumsum(dtc * A, axis=1)                         # (B, C, H)
        last = a[:, -1]                                         # (B, H)
        xg = xc.reshape(B, C, G, K, P)
        # Inside the sub-chunk: pairs (t, i), i <= t, a group's C B^T once.
        cb = jnp.einsum("btgn,bign->bgti", cc, bc,
                        preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            a[:, :, None, :] - a[:, None, :, :], -jnp.inf))     # b t i h
        w = cb[:, :, None] * (decay * dtc[:, None]).transpose(
            0, 3, 1, 2).reshape(B, G, K, C, C)                  # b g k t i
        y = jnp.einsum("bgkti,bigkp->btgkp", w.astype(xc.dtype), xg,
                       preferred_element_type=f32)
        # What came before it: the state, decayed up to each position.
        Sg = S.reshape(B, G, K, P, N)
        read = jnp.einsum("btgn,bgkpn->btgkp", cc, Sg.astype(cc.dtype),
                          preferred_element_type=f32)
        y = y + jnp.exp(a).reshape(B, C, G, K)[..., None] * read
        # The state after it: each position decayed to the sub-chunk's end.
        left = (jnp.exp(last[:, None] - a) * dtc).reshape(B, C, G, K)
        Sg = jnp.exp(last).reshape(B, G, K)[..., None, None] * Sg \
            + jnp.einsum("bigkp,bign->bgkpn",
                         (xg.astype(f32) * left[..., None]).astype(xc.dtype),
                         bc, preferred_element_type=f32)
        return Sg.reshape(B, H, P, N), y.reshape(B, C, H, P)

    state, y = jax.lax.scan(body, state.astype(f32),
                            tuple(cut(a) for a in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, -1, H, P)[:, :T]
    return y + D.astype(f32)[:, None] * x[:, :T].astype(f32), state


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
