"""Power retention (arXiv:2507.04239, "Scaling Context Requires Rethinking
Attention") of degree 2 with a gate a key-value head, with a state carried
in and out. For query head ``h`` of key-value head ``j``:

    a[t, i] = (s q_t^h . k_i^j)^2 exp(gam_{i+1} + ... + gam_t)     i <= t
    o_t^h   = sum_i a[t, i] v_i^j / (sum_i a[t, i] + eps)

``phi(x)`` is the symmetric second power of ``x``, so that ``phi(q) .
phi(k) = (q . k)^2``, and a key-value head keeps

    S_t = e^{gam_t} S_{t-1} + v_t phi(k_t)^T        (d x R, float32)
    z_t = e^{gam_t} z_{t-1} + phi(k_t)              (R, float32)
    o_t^h = S_t phi(s q_t^h) / (z_t . phi(s q_t^h) + eps)

The layout held. ``phi`` cuts the ``d`` numbers of a head into ``d /
block`` blocks and keeps the block pairs ``(A, B)``, ``A <= B``: a diagonal
pair whole (both ``x_a x_b`` and ``x_b x_a``), the others once, times sqrt
2. At ``d`` = 128 and ``block`` = 16 that is 36 pairs of 256 = 9,216 rows
``R``, whole 128-lane tiles, where the 8,256 distinct monomials are not;
the full tensor product would be 16,384. The state lies ``(d, R)``, a
value's 128 numbers on sublanes and the monomials on lanes: the decode
kernel's update ``v phi(k)^T`` then spreads ``phi(k)`` along sublanes,
which costs nothing, where ``(R, d)`` would need ``phi(k)`` as a column.

``s`` never touches an operand: numerator and denominator both carry ``s^2``,
so ``o = N / (D + eps / s^2)`` of the unscaled sums.

* ``retention_chunk``: a prefill chunk. Sub-chunks of ``SUB_CHUNK`` = 128
  positions; inside one the quadratic form with its decay mask (2.6 MFLOP
  a token a layer at the served widths), across them the state (113). The
  operands of every matmul are in the inputs' dtype, state and accumulation
  float32. 2,048 positions of one row take 5.6 ms a layer at 128, 8.4 at
  256 and 512, 10.6 at 64 (``microbench_retention.py``, PR 52): XLA writes
  a sub-chunk's expanded queries (0.74 MB a token) to HBM whatever the
  length, and the pairs' float32 elementwise work grows with it.
* ``retention_step``: the decode's one token a stepping slot, a Pallas
  kernel over ``(slot, key-value head, tile of R)`` that fetches a state
  tile once, scales it, adds ``v phi(k)^T``, takes the group's query heads'
  products from it while it is in VMEM and writes it back where it lay
  (``input_output_aliases``). A slot outside the step is not read and not
  written: its grid steps name one tile of the scratch row and copy it.
  (XLA's fusion of the same expression takes 3.4 x the kernel's time on the
  chip, PR 52; it lives on as ``tests/test_power_retention.py::step_jnp``,
  what the kernel is held to and ``microbench_retention.py`` times it
  against.)"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 16          # numbers of a head in one block of ``phi``
SUB_CHUNK = 128     # positions the quadratic form covers at once
EPS = 1e-6
NAME = "retention_step"
# Bytes of one state tile the kernel holds (in and out, twice each).
_TILE_BYTES = 2 << 20


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernel runs in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _pairs(head_dim: int, block: int):
    """Block pairs ``(A, B)``, ``A <= B``, and each pair's weight."""
    if head_dim % block:
        raise ValueError(f"a head of {head_dim} is not whole blocks of "
                         f"{block}")
    first, second = np.triu_indices(head_dim // block)
    weight = np.where(first == second, 1.0, math.sqrt(2.0))
    return first, second, weight.astype(np.float32)


def phi_rows(head_dim: int, block: int = BLOCK) -> int:
    """Rows ``R`` of the expansion as held."""
    n = head_dim // block
    return n * (n + 1) // 2 * block * block


def phi(x: jax.Array, block: int = BLOCK) -> jax.Array:
    """(..., d) -> (..., R) in ``x``'s dtype: the symmetric second power in
    the layout held."""
    first, second, weight = _pairs(x.shape[-1], block)
    xb = x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))
    out = (xb[..., first, :, None] * xb[..., second, None, :]
           * jnp.asarray(weight, x.dtype)[:, None, None])
    return out.reshape(x.shape[:-1] + (-1,))


@functools.lru_cache(maxsize=None)
def _selectors(head_dim: int, block: int):
    """``(E1, E2, w)``: row ``r`` of the expansion is ``w[r] x[E1's one in
    column r] x[E2's]``, the two (d, R) matrices 0/1."""
    first, second, weight = _pairs(head_dim, block)
    within = np.arange(block)
    a = (first[:, None] * block + np.repeat(within, block)).reshape(-1)
    b = (second[:, None] * block + np.tile(within, block)).reshape(-1)
    rows = np.arange(a.size)
    e1 = np.zeros((head_dim, a.size), np.float32)
    e2 = np.zeros((head_dim, a.size), np.float32)
    e1[a, rows] = 1.0
    e2[b, rows] = 1.0
    return e1, e2, np.repeat(weight, block * block)


def phi_lanes(x: jax.Array, block: int = BLOCK) -> jax.Array:
    """``phi`` for a FEW rows (a decode step's): both factors picked by
    0/1 matmuls, which copy exactly and leave the rows of the expansion on
    lanes as they come, where ``phi``'s reshape of (pairs, block, block)
    is a relayout that costs a step more than the expansion itself. Three
    times ``phi``'s matmul work: not for a chunk."""
    e1, e2, weight = _selectors(x.shape[-1], block)
    pick = functools.partial(jnp.einsum, "...d,dr->...r", x,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    out = pick(jnp.asarray(e1, x.dtype)) * pick(jnp.asarray(e2, x.dtype)) \
        * jnp.asarray(weight)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ a chunk


def retention_chunk(q, k, v, log_g, S, z, *, scale: float,
                    block: int = BLOCK, sub_chunk: int = SUB_CHUNK,
                    eps: float = EPS, lengths=None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``T`` positions a row from the state before the first. ``q`` (B, T,
    J, G, d); ``k``, ``v`` (B, T, J, d); ``log_g`` (B, T, J) float32, the
    gate's logarithm; ``S`` (B, J, d, R) and ``z`` (B, J, R) float32 (zeros
    for a row that starts at position 0); ``lengths`` (B,) the real
    positions of each row: the rest leave the state as it is and their
    output is junk. Returns ``(o (B, T, J, G, d) float32, S, z)`` after
    each row's last real position."""
    B, T, J, G, d = q.shape
    if lengths is not None:
        real = jnp.arange(T)[None, :, None] < lengths[:, None, None]
        log_g = jnp.where(real, log_g, 0.0)
        k = jnp.where(real[..., None], k, 0)
    C = min(sub_chunk, T)
    short = -T % C
    if short:
        q, k, v, log_g = (jnp.pad(a, [(0, 0), (0, short)]
                                  + [(0, 0)] * (a.ndim - 2))
                          for a in (q, k, v, log_g))
    f32 = jnp.float32
    eps = eps / scale ** 2
    causal = jnp.tril(jnp.ones((C, C), bool))
    # ``z`` rides as one more row of values under ``S``: the expanded
    # queries and keys, the largest arrays here, are then read once each.
    Sz = jnp.concatenate([S, z[:, :, None, :]], axis=2)        # b j d+1 r

    def cut(a):
        """(B, T, ...) -> (T / C, B, C, ...), sub-chunks leading."""
        return jnp.moveaxis(a.reshape((B, -1, C) + a.shape[2:]), 1, 0)

    def body(Sz, inp):
        qc, kc, vc, gc = inp
        cum = jnp.cumsum(gc.astype(f32), axis=1)               # (B, C, J)
        total = cum[:, -1]                                     # (B, J)
        # Inside the sub-chunk: pairs (t, i), i <= t.
        s = jnp.einsum("btjgd,bijd->bjgti", qc, kc,
                       preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        a = s * s * decay.transpose(0, 3, 1, 2)[:, :, None]    # b j g t i
        num = jnp.einsum("bjgti,bijd->btjgd", a.astype(vc.dtype), vc,
                         preferred_element_type=f32)
        den = a.sum(-1).transpose(0, 3, 1, 2)                  # (B, C, J, G)
        # What came before it: the state, decayed up to each position.
        fq = phi(qc, block)                                    # b t j g r
        read = jnp.exp(cum)[..., None, None] * jnp.einsum(
            "btjgr,bjdr->btjgd", fq, Sz.astype(fq.dtype),
            preferred_element_type=f32)
        o = (num + read[..., :d]) / (den + read[..., d] + eps)[..., None]
        # The state after it: each position decayed to the sub-chunk's end.
        fk = phi(kc, block)                                    # b i j r
        left = jnp.exp(total[:, None] - cum)[..., None]        # b i j 1
        weighed = jnp.concatenate([vc.astype(f32) * left, left], axis=-1)
        Sz = jnp.exp(total)[..., None, None] * Sz + jnp.einsum(
            "bijd,bijr->bjdr", weighed.astype(vc.dtype), fk,
            preferred_element_type=f32)
        return Sz, o

    Sz, o = jax.lax.scan(body, Sz, tuple(cut(a) for a in (q, k, v, log_g)))
    o = jnp.moveaxis(o, 0, 1).reshape((B, -1) + o.shape[3:])[:, :T]
    return o, Sz[:, :, :d], Sz[:, :, d]


# -------------------------------------------------------------- one token


def _row_tile(rows: int, head_dim: int) -> int:
    """Lanes of ``R`` one grid step holds: the largest whole number of
    128-lane tiles that divides ``rows`` within ``_TILE_BYTES``; all of a
    toy's."""
    best = 0
    for lanes in range(128, rows + 1, 128):
        if rows % lanes == 0 and head_dim * lanes * 4 <= _TILE_BYTES:
            best = lanes
    return best or rows


def _step_kernel(at_ref, rows_ref, live_ref, s_ref, aux_ref, fk_ref, fq_ref,
                 s_out, o_out, *, head_dim: int):
    del at_ref, rows_ref
    b, r = pl.program_id(0), pl.program_id(2)
    live = live_ref[b] > 0

    @pl.when(r == 0)
    def _():
        o_out[...] = jnp.zeros_like(o_out)

    @pl.when(jnp.logical_not(live))
    def _():
        s_out[...] = s_ref[...]

    @pl.when(live)
    def _():
        # ``aux``: a value's numbers on sublanes, the same in every lane,
        # and under them the gate, the same everywhere.
        vb = aux_ref[:head_dim, :]                              # (d, 128)
        gate = aux_ref[head_dim:head_dim + 1, :]                # (1, 128)
        lanes = s_ref.shape[-1]
        step = min(128, lanes)
        for c in range(0, lanes, step):
            w = min(step, lanes - c)
            s_out[:, c:c + w] = (s_ref[:, c:c + w] * gate[:, :w]
                                 + vb[:, :w] * fk_ref[:, c:c + w])
        fq = fq_ref[...]                                        # (G', lanes)
        o_out[...] += jax.lax.dot_general(
            fq, s_out[...].astype(fq.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _step_state(fq, fk, v, gate, S, layer, steps):
    """The kernel: ``fq`` (B, J, G, R) in the compute dtype, ``fk`` (B, J,
    R), ``v`` (B, J, d) and ``gate`` (B, J) float32, ``S`` the whole leaf
    (layers, slots + 1, J, d, R). Returns the numerators (B, J, G, d)
    float32 (zeros for a slot outside ``steps``) and the leaf."""
    B, J, G, R = fq.shape
    d = S.shape[3]
    lanes = _row_tile(R, d)
    held = -(-G // 8) * 8
    fq = jnp.pad(fq, [(0, 0), (0, 0), (0, held - G), (0, 0)])
    width = min(128, lanes)
    aux = jnp.concatenate([
        jnp.broadcast_to(v[..., None], (B, J, d, width)),
        jnp.broadcast_to(gate[..., None, None], (B, J, 8, width))],
        axis=2).astype(jnp.float32)
    scratch = S.shape[1] - 1
    live = steps.astype(jnp.int32)
    rows = jnp.where(steps, jnp.arange(B, dtype=jnp.int32), scratch)

    def state_map(b, j, r, at, rows, live):
        # A slot outside the step names ONE tile of the scratch row at
        # every grid step of its own: fetched and written back once.
        return at[0], rows[b], j * live[b], 0, r * live[b]

    def head_map(b, j, r, *_):
        return b, j, 0, 0

    def tile_map(b, j, r, *_):
        return b, j, 0, r

    state = pl.BlockSpec((None, None, None, d, lanes), state_map)
    S, num = pl.pallas_call(
        functools.partial(_step_kernel, head_dim=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, J, R // lanes),
            in_specs=[
                state,
                pl.BlockSpec((None, None, d + 8, width), head_map),
                pl.BlockSpec((None, None, 1, lanes), tile_map),
                pl.BlockSpec((None, None, held, lanes), tile_map),
            ],
            out_specs=[
                state,
                pl.BlockSpec((None, None, held, d), head_map),
            ]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, J, held, d), jnp.float32)],
        # Operand 3 (behind the three prefetched scalars) is the state.
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, live, S, aux,
      fk[:, :, None, :].astype(jnp.float32), fq)
    return num[:, :, :G], S


def _step_inputs(q, k, log_g, block):
    f32 = jnp.float32
    return (phi_lanes(q, block), phi_lanes(k.astype(f32), block),
            jnp.exp(log_g.astype(f32)))


def _step_normaliser(fq, fk, gate, z, layer, steps):
    """``z`` is a 128th of the state: its update and the denominators are
    XLA's in both forms. Returns ``(den (B, J, G), z)``."""
    B = fq.shape[0]
    z0 = jax.lax.dynamic_index_in_dim(z, layer, 0, False)[:B]
    z1 = gate[..., None] * z0 + fk
    den = jnp.einsum("bjgr,bjr->bjg", fq.astype(jnp.float32), z1,
                     precision=jax.lax.Precision.HIGHEST)
    z1 = jnp.where(steps[:, None, None], z1, z0)
    return den, jax.lax.dynamic_update_slice(z, z1[None], (layer, 0, 0, 0))


def retention_step(q, k, v, log_g, S, z, steps, layer=0, *, scale: float,
                   block: int = BLOCK, eps: float = EPS
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a slot. ``q`` (B, J, G, d); ``k``, ``v`` (B, J, d);
    ``log_g`` (B, J); ``S`` (layers, slots + 1, J, d, R) and ``z`` (layers,
    slots + 1, J, R) the state LEAVES, of which rows ``[layer, :B]`` are
    read and written where they lie; ``steps`` (B,) bool: a slot outside it
    keeps its state bit for bit and gets zeros. Returns ``(o (B, J, G, d)
    float32, S, z)``."""
    with jax.named_scope(NAME):
        fq, fk, gate = _step_inputs(q, k, log_g, block)
        num, S = _step_state(fq, fk, v.astype(jnp.float32), gate, S, layer,
                             steps)
        den, z = _step_normaliser(fq, fk, gate, z, layer, steps)
        return num / (den + eps / scale ** 2)[..., None], S, z
