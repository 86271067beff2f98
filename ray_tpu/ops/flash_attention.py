"""Pallas TPU flash/splash attention: fused, tiled, O(S) memory, custom VJP.

The TPU-native replacement for the flash/splash attention kernels the
reference world gets from CUDA libraries (its integrations defer to torch
SDPA; SURVEY §5.7 requires the TPU build to make these kernels first-class).
Design, per the Pallas TPU playbook:

* Layout (B, H, S, D): the (S, D) minor tile maps q/k/v blocks straight onto
  (sublane, lane) tiling; D is padded to a lane multiple (128) when needed.
* Forward: online-softmax over KV tiles with fp32 accumulators in VMEM
  scratch; emits the log-sum-exp alongside the output so the backward can
  recompute probabilities without ever materializing the (S, S) score
  matrix.
* Backward: two kernels with flash-attention-2 style recomputation — one
  accumulates dK/dV (grid minor axis = query tiles), one accumulates dQ
  (grid minor axis = KV tiles). ``delta = rowsum(dO * O)`` is a cheap
  elementwise pass left to XLA.
* SPLASH-style block sparsity: causal masking, a sliding ``window``, and
  ``segment_ids`` compose. Causal/window masks skip fully-dead tiles with
  ``pl.when`` by tile arithmetic (no compute, only the pipelined fetch), so
  local attention costs O(S * window) not O(S^2); partial tiles and segment
  boundaries mask in-register. ``q_offset`` shifts the causal/window
  frontier so ring attention / decode reuse the same kernel per shard.
* GQA: the KV head for a query head is selected in the BlockSpec index map
  (``h // group``) — the repeat never materializes.
* ``flash_attention_stats`` returns (out, lse) with a VJP that accepts a
  cotangent for lse (``ds += p * g_lse``) — the hook ring attention's
  cross-shard online-softmax merge differentiates through.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANE = 128  # TPU lane width: minor dim of every block must divide into it
_SUBLANE = 8  # second-minor tile of a 32-bit block


def _interpret() -> bool:
    """Off-TPU (the CPU tests) the kernels run in the Pallas interpreter;
    on the chip they lower through Mosaic (``chip_smoke.py`` checks the
    train step's HLO for the ``tpu_custom_call``)."""
    return jax.default_backend() != "tpu"


def _block_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ------------------------------------------------------------------ masks


def _tile_live(i, j, block_q, block_k, q_offset, causal, window):
    """Is any (row, col) of tile (i, j) unmasked by the causal/window
    bands? Segment masks are data-dependent and never skip tiles."""
    row_min = q_offset + i * block_q
    row_max = row_min + block_q - 1
    col_min = j * block_k
    col_max = col_min + block_k - 1
    live = True
    if causal:
        live = jnp.logical_and(live, row_max >= col_min)
    if window is not None:
        # Sliding window keeps cols in (row - window, row].
        live = jnp.logical_and(live, col_max > row_min - window)
    return live


def _mask_scores(s, i, j, block_q, block_k, q_offset, causal, window,
                 seg_q=None, seg_k=None):
    if not causal and window is None and seg_q is None:
        return s
    rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if causal:
        s = jnp.where(rows >= cols, s, _NEG_INF)
    if window is not None:
        s = jnp.where(rows - cols < window, s, _NEG_INF)
    if seg_q is not None:
        # seg ids ride as fp32 (exact for ids < 2^24), equality only:
        # seg_q (block_q, LANE) read at lane 0, seg_k (SUBLANE, block_k)
        # read at sublane 0 — the tileable layouts _seg_layout builds.
        s = jnp.where(seg_q[:, 0:1] == seg_k[0:1, :], s, _NEG_INF)
    return s


def _seg_layout(seg_q, seg_k):
    """(B, S) segment ids -> layouts Mosaic can tile: query ids broadcast
    along the lane axis (B, Sq, LANE), key ids along the sublane axis
    (B, SUBLANE, Sk). A (1, block) block over a (B, S) array is refused
    (second-minor block dim 1 is neither a multiple of 8 nor B)."""
    b, sq = seg_q.shape
    sk = seg_k.shape[1]
    return (jnp.broadcast_to(seg_q[:, :, None], (b, sq, _LANE)),
            jnp.broadcast_to(seg_k[:, None, :], (b, _SUBLANE, sk)))


# ---------------------------------------------------------------- forward


def _fwd_kernel(*refs, scale, block_q, block_k, causal, window, q_offset,
                segmented):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        sq_ref = sk_ref = None
    i = pl.program_id(2)  # query tile
    j = pl.program_id(3)  # kv tile
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = _tile_live(i, j, block_q, block_k, q_offset, causal, window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]  # (block_q, D)
        k = k_ref[0, 0]  # (block_k, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_scores(
            s, i, j, block_q, block_k, q_offset, causal, window,
            None if sq_ref is None else sq_ref[0],
            None if sk_ref is None else sk_ref[0])
        m_prev = m_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.maximum(m_new, _NEG_INF / 2)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(jnp.maximum(m_prev, _NEG_INF / 2) - m_safe)
        l_ref[:, 0:1] = l_ref[:, 0:1] * corr + jnp.sum(p, axis=-1,
                                                       keepdims=True)
        m_ref[:, 0:1] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == nk - 1)
    def _final():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        m = m_ref[:, 0:1]
        lse = jnp.where(
            l == 0.0, _NEG_INF,
            jnp.maximum(m, _NEG_INF / 2) + jnp.log(l_safe))
        # TPU blocks need a 128-lane minor dim: lse is broadcast across the
        # lane axis (same trick as jax's in-tree kernel); readers use lane 0.
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], _LANE))


def _fwd(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
         block_q, block_k):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    nq, nk = sq // block_q, sk // block_k
    grid = (b, h, nq, nk)
    segmented = seg_q is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, q_offset=q_offset, segmented=segmented)
    in_specs = [
        _block_spec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        _block_spec((1, 1, block_k, d),
                    lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        _block_spec((1, 1, block_k, d),
                    lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            _block_spec((1, block_q, _LANE), lambda b_, h_, i, j: (b_, i, 0)),
            _block_spec((1, _SUBLANE, block_k),
                        lambda b_, h_, i, j: (b_, 0, j)),
        ]
        args += list(_seg_layout(seg_q, seg_k))
    # The scope and the kernel's name are what a device trace carries: a
    # reader finds the three kernels by them, not by XLA's numbering.
    with jax.named_scope("flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                _block_spec((1, 1, block_q, d),
                            lambda b_, h_, i, j: (b_, h_, i, 0)),
                _block_spec((1, 1, block_q, _LANE),
                            lambda b_, h_, i, j: (b_, h_, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, sq, _LANE), jnp.float32),
            ],
            scratch_shapes=[
                _scratch((block_q, d), jnp.float32),
                _scratch((block_q, 128), jnp.float32),
                _scratch((block_q, 128), jnp.float32),
            ],
            interpret=_interpret(),
            name="flash_fwd",
        )(*args)
    # Keep only lane 0 (the value; other lanes are the tiling broadcast) so
    # the residual saved for the backward is (B, H, S), not 128x that.
    return out, lse[..., 0]


# --------------------------------------------------------------- backward


def _bwd_dkv_kernel(*refs, scale, block_q, block_k, causal, window, q_offset,
                    segmented, has_dlse):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    dlse_ref = next(it) if has_dlse else None
    sq_ref = next(it) if segmented else None
    sk_ref = next(it) if segmented else None
    dk_ref, dv_ref, dk_acc, dv_acc = next(it), next(it), next(it), next(it)
    i = pl.program_id(3)  # query tile (minor)
    j = pl.program_id(2)  # kv tile
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = _tile_live(i, j, block_q, block_k, q_offset, causal, window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]          # (bq, D)
        k = k_ref[0, 0]          # (bk, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # (bq, D)
        lse = lse_ref[0, 0][:, 0:1]      # (bq, 1); lane-0 of padded layout
        delta = delta_ref[0, 0][:, 0:1]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_scores(
            s, i, j, block_q, block_k, q_offset, causal, window,
            None if sq_ref is None else sq_ref[0],
            None if sk_ref is None else sk_ref[0])
        p = jnp.exp(s - jnp.maximum(lse, _NEG_INF / 2))  # (bq, bk)
        # dV += P^T dO
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP = dO V^T ; dS = P * (dP - delta [+ g_lse]) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dlse_ref is not None:
            dp = dp + dlse_ref[0, 0][:, 0:1]
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _final():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, block_q, block_k, causal, window, q_offset,
                   segmented, has_dlse):
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    dlse_ref = next(it) if has_dlse else None
    sq_ref = next(it) if segmented else None
    sk_ref = next(it) if segmented else None
    dq_ref, dq_acc = next(it), next(it)
    i = pl.program_id(2)  # query tile
    j = pl.program_id(3)  # kv tile (minor)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = _tile_live(i, j, block_q, block_k, q_offset, causal, window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_scores(
            s, i, j, block_q, block_k, q_offset, causal, window,
            None if sq_ref is None else sq_ref[0],
            None if sk_ref is None else sk_ref[0])
        p = jnp.exp(s - jnp.maximum(lse, _NEG_INF / 2))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dlse_ref is not None:
            dp = dp + dlse_ref[0, 0][:, 0:1]
        ds = (p * (dp - delta) * scale)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd(q, k, v, seg_q, seg_k, out, lse, do, dlse, scale, causal, window,
         q_offset, block_q, block_k):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    nq, nk = sq // block_q, sk // block_k
    segmented = seg_q is not None
    has_dlse = dlse is not None

    # (B, H, S, LANE): lse and delta broadcast across the lane axis so their
    # blocks are TPU-tileable (kernels read lane 0).
    lse = jnp.broadcast_to(lse[..., None], (b, h, sq, _LANE))
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True),
        (b, h, sq, _LANE))
    extra = []
    if has_dlse:
        extra.append(jnp.broadcast_to(
            dlse.astype(jnp.float32)[..., None], (b, h, sq, _LANE)))
    if segmented:
        extra += list(_seg_layout(seg_q, seg_k))

    def lane_spec(index_map):
        return _block_spec((1, 1, block_q, _LANE), index_map)

    # dK/dV: one (b, kv-head, kv-tile) program accumulates over all query
    # tiles of every query head in the group (GQA reduction folded into the
    # grid's minor axis).
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, q_offset=q_offset,
        segmented=segmented, has_dlse=has_dlse)
    grid_dkv = (b, h, nk, nq)
    qmap = lambda b_, h_, j, i: (b_, h_, i, 0)        # noqa: E731
    kmap = lambda b_, h_, j, i: (b_, h_ // group, j, 0)  # noqa: E731
    in_specs = [
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_q, d), qmap),
        lane_spec(qmap),
        lane_spec(qmap),
    ]
    if has_dlse:
        in_specs.append(lane_spec(qmap))
    if segmented:
        in_specs += [
            _block_spec((1, block_q, _LANE), lambda b_, h_, j, i: (b_, i, 0)),
            _block_spec((1, _SUBLANE, block_k),
                        lambda b_, h_, j, i: (b_, 0, j)),
        ]
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=grid_dkv,
            in_specs=in_specs,
            out_specs=[
                _block_spec((1, 1, block_k, d),
                            lambda b_, h_, j, i: (b_, h_, j, 0)),
                _block_spec((1, 1, block_k, d),
                            lambda b_, h_, j, i: (b_, h_, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
                jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            ],
            scratch_shapes=[
                _scratch((block_k, d), jnp.float32),
                _scratch((block_k, d), jnp.float32),
            ],
            interpret=_interpret(),
            name="flash_bwd_dkv",
        )(q, k, v, do, lse, delta, *extra)
    if group > 1:
        dk = dk.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, sk, d).sum(axis=2)
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, q_offset=q_offset,
        segmented=segmented, has_dlse=has_dlse)
    grid_dq = (b, h, nq, nk)
    qmap2 = lambda b_, h_, i, j: (b_, h_, i, 0)          # noqa: E731
    kmap2 = lambda b_, h_, i, j: (b_, h_ // group, j, 0)  # noqa: E731
    in_specs = [
        _block_spec((1, 1, block_q, d), qmap2),
        _block_spec((1, 1, block_k, d), kmap2),
        _block_spec((1, 1, block_k, d), kmap2),
        _block_spec((1, 1, block_q, d), qmap2),
        lane_spec(qmap2),
        lane_spec(qmap2),
    ]
    if has_dlse:
        in_specs.append(lane_spec(qmap2))
    if segmented:
        in_specs += [
            _block_spec((1, block_q, _LANE), lambda b_, h_, i, j: (b_, i, 0)),
            _block_spec((1, _SUBLANE, block_k),
                        lambda b_, h_, i, j: (b_, 0, j)),
        ]
    with jax.named_scope("flash_bwd_dq"):
        dq = pl.pallas_call(
            dq_kernel,
            grid=grid_dq,
            in_specs=in_specs,
            out_specs=[
                _block_spec((1, 1, block_q, d), qmap2),
            ],
            out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
            scratch_shapes=[_scratch((block_q, d), jnp.float32)],
            interpret=_interpret(),
            name="flash_bwd_dq",
        )(q, k, v, do, lse, delta, *extra)[0]
    return dq, dk, dv


# ------------------------------------------------------------- custom VJPs


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
           block_q, block_k):
    out, _ = _fwd(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
                  block_q, block_k)
    return out


def _flash_fwd(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
               block_q, block_k):
    out, lse = _fwd(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
                    block_q, block_k)
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _flash_bwd(scale, causal, window, q_offset, block_q, block_k, res, g):
    q, k, v, seg_q, seg_k, out, lse = res
    dq, dk, dv = _bwd(q, k, v, seg_q, seg_k, out, lse, g, None, scale,
                      causal, window, q_offset, block_q, block_k)
    zseg = (None if seg_q is None else jnp.zeros_like(seg_q),
            None if seg_k is None else jnp.zeros_like(seg_k))
    return (dq, dk, dv) + zseg


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_stats(q, k, v, scale, causal, window, q_offset,
                          block_q, block_k) -> Tuple[jax.Array, jax.Array]:
    """(out, lse) with a VJP accepting cotangents for both. Shapes are
    (B, H, S, D) / (B, H, S); used by ring attention's cross-shard merge."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def stats(q, k, v):
        return _fwd(q, k, v, None, None, scale, causal, window, q_offset,
                    block_q, block_k)

    def stats_fwd(q, k, v):
        out, lse = _fwd(q, k, v, None, None, scale, causal, window, q_offset,
                        block_q, block_k)
        return (out, lse), (q, k, v, out, lse)

    def stats_bwd(res, cotangents):
        g, g_lse = cotangents
        q, k, v, out, lse = res
        dq, dk, dv = _bwd(q, k, v, None, None, out, lse, g, g_lse, scale,
                          causal, window, q_offset, block_q, block_k)
        return dq, dk, dv

    stats.defvjp(stats_fwd, stats_bwd)
    return stats(q, k, v)


# ------------------------------------------------------------- public API


def flash_attention(
    q: jax.Array,                # (B, S, Hq, D)
    k: jax.Array,                # (B, S, Hkv, D)
    v: jax.Array,                # (B, S, Hkv, D)
    causal: bool = True,
    q_offset: int = 0,
    block_q: int = 256,
    block_k: int = 256,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,      # (B, S) int
    kv_segment_ids: Optional[jax.Array] = None,   # (B, S_kv) int
) -> jax.Array:
    """Flash attention over (batch, seq, heads, head_dim) tensors.

    Drop-in for ``ray_tpu.ops.attention.attention`` (same signature shape);
    differentiable via the fused Pallas backward. ``window`` keeps only the
    last ``window`` positions per query (sliding-window/local attention —
    dead tiles are skipped, so cost is O(S*window)); ``segment_ids`` masks
    cross-segment attention (packed sequences), splash-style.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = d ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq}, {sk}) must divide blocks ({block_q}, "
            f"{block_k})")

    # (B, S, H, D) -> (B, H, S, D): puts (S, D) on the (sublane, lane) tile.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # Lane-align head_dim (zero-pad is exact: scores unchanged, padded
    # output columns are sliced off).
    d_pad = (-d) % 128
    if d_pad:
        pad = [(0, 0), (0, 0), (0, 0), (0, d_pad)]
        qt = jnp.pad(qt, pad)
        kt = jnp.pad(kt, pad)
        vt = jnp.pad(vt, pad)

    seg_q = seg_k = None
    if kv_segment_ids is not None and segment_ids is None:
        raise ValueError(
            "kv_segment_ids requires segment_ids (the query-side ids); "
            "pass both to mask packed cross-attention")
    if segment_ids is not None:
        # fp32 ids: exact equality for ids < 2^24, and the cotangent space
        # stays float (custom_vjp needs a concrete zero to return).
        seg_q = segment_ids.astype(jnp.float32)
        seg_k = (segment_ids if kv_segment_ids is None
                 else kv_segment_ids).astype(jnp.float32)

    out = _flash(qt, kt, vt, seg_q, seg_k, scale, causal, window, q_offset,
                 block_q, block_k)
    if d_pad:
        out = out[..., :d]
    return out.transpose(0, 2, 1, 3)
