"""Pallas TPU kernel for the PREFILL form of multi-head latent attention
(``models/deepseek_decode.py``): many queries against per-head keys and
values that were up-projected from the cached latents, plus one rotary key
row that all heads share.

Plain XLA runs this attention bound by memory: every (query, key, head)
score is written to HBM in float32 and read back for the maximum, the
exponential and the value product, ~16 B against 640 operations, where the
chip has 240 operations a byte. On a TPU v5e a 2,048-token chunk over 8k of
context spent 85 ms a layer there (PERF.md, PR 36). Here the scores of one
(query block, key block) tile live in VMEM only: the running softmax of
``ops/flash_attention.py``, forward only, with what latent attention needs
beside it:

* two score terms, ``q_nope . k_nope`` per head and ``q_pe . k_pe`` with
  ``k_pe`` read once for all heads (its block index ignores the head);
* a causal frontier that starts at a TRACED offset per row (a chunk's
  queries sit at ``prefix_len + i``; one compiled program serves every
  prefix), brought in by scalar prefetch. Tiles wholly above the frontier
  are skipped, and their block index is clamped to the last live one so
  that not even their fetch is issued;
* values of their own width (128 against the keys' 192).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANE = 128
# The tile of one grid step: queries x keys whose scores live in VMEM.
BLOCK_Q = 512
BLOCK_K = 1024


def _kernel(off_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, block_q, block_k):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    first_row = off_ref[b] + i * block_q

    # Live: the tile's last query sees the tile's first key.
    @pl.when(first_row + block_q - 1 >= j * block_k)
    def _tile():
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp_ref[0, 0], kp_ref[0], dims,
                                   preferred_element_type=jnp.float32))
        rows = first_row + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s * scale, _NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Key 0 is visible to every query, so after the first tile the
        # maximum is a real score and a masked entry's exp is 0.
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1,
                                                       keepdims=True)
        m_ref[:, 0:1] = m_new
        v = v_ref[0, 0]
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _final():
        l = l_ref[:, 0:1]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def _interpret() -> bool:
    """Off the TPU (the CPU tests) the kernel runs in the Pallas
    interpreter."""
    return jax.default_backend() != "tpu"


def _pad_lanes(x):
    short = -x.shape[-1] % _LANE
    if not short:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def latent_prefill_attention(q_nope, q_pe, k_nope, k_pe, v, offsets,
                             scale: float):
    """Causal attention of ``S`` queries a row over ``C`` keys.

    ``q_nope`` (B, H, S, Dn), ``q_pe`` (B, H, S, Dr), ``k_nope`` (B, H, C,
    Dn), ``k_pe`` (B, C, Dr) shared by the heads, ``v`` (B, H, C, Dv);
    ``offsets`` (B,) int32: query ``i`` of row ``b`` sits at position
    ``offsets[b] + i`` and sees the keys at positions up to its own.
    Scores are ``(q_nope . k_nope + q_pe . k_pe) * scale``, softmax and
    accumulation in float32, probabilities rounded to ``v``'s dtype for
    the value product. Returns (B, H, S, Dv) in ``q_nope``'s dtype. Off
    the TPU the kernel runs in the Pallas interpreter."""
    B, H, S, _ = q_nope.shape
    C, dv = k_nope.shape[2], v.shape[-1]
    # Whole blocks: the engine's buckets and page windows are powers of
    # two, where these are just the smaller of the two numbers.
    block_q, block_k = math.gcd(S, BLOCK_Q), math.gcd(C, BLOCK_K)
    q_pe, k_pe = _pad_lanes(q_pe), _pad_lanes(k_pe)
    dn, dr = q_nope.shape[-1], q_pe.shape[-1]

    def q_map(b, h, i, j, off):
        return b, h, i, 0

    def last_live(b, i, off):
        return (off[b] + (i + 1) * block_q - 1) // block_k

    def k_map(b, h, i, j, off):
        return b, h, jnp.minimum(j, last_live(b, i, off)), 0

    def kpe_map(b, h, i, j, off):
        return b, jnp.minimum(j, last_live(b, i, off)), 0

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    kernel = functools.partial(_kernel, scale=scale, block_q=block_q,
                               block_k=block_k)
    with jax.named_scope("latent_attn"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, H, S // block_q, C // block_k),
                in_specs=[
                    spec((1, 1, block_q, dn), q_map),
                    spec((1, 1, block_q, dr), q_map),
                    spec((1, 1, block_k, dn), k_map),
                    spec((1, block_k, dr), kpe_map),
                    spec((1, 1, block_k, dv), k_map),
                ],
                out_specs=spec((1, 1, block_q, dv), q_map),
                scratch_shapes=[
                    pltpu.VMEM((block_q, dv), jnp.float32),
                    pltpu.VMEM((block_q, _LANE), jnp.float32),
                    pltpu.VMEM((block_q, _LANE), jnp.float32),
                ]),
            out_shape=jax.ShapeDtypeStruct((B, H, S, dv), q_nope.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=_interpret(),
            name="latent_attn",
        )(offsets.astype(jnp.int32), q_nope, q_pe, k_nope, k_pe, v)
