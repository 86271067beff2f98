"""Splash attention: schedule-driven block-sparse flash attention (Pallas).

"Splash" = SParse fLASH. :mod:`ray_tpu.ops.flash_attention` takes ONE mask
algebra for every head (causal, a window, a query offset, segment ids) and
walks a flat list of its live tiles; this module builds **per-head static
mask schedules** (the defining structure of the reference-world splash
kernel, cf. jax's
``splash_attention_kernel.py``/``splash_attention_mask_info.py`` — studied
for the schedule idea, implemented independently on this repo's kernel
style):

* a :class:`Mask` describes one head's static sparsity (causal, local
  window, chunked/block-diagonal, full);
* heads with different masks are grouped, and for each group the trace-time
  schedule lists, per q-tile, EXACTLY the live kv-tiles —
  ``kv_ids[nq, L]`` + ``lens[nq]`` ride to the kernel as scalar-prefetch
  operands, so the grid's minor axis walks the compacted schedule and dead
  tiles are never fetched. The schedule is a RECTANGLE (``L`` = the longest
  row; shorter rows pad with steps that hold their last tile), where the
  flash kernels' is flat: for a plain causal mask ``L`` is every KV tile,
  so that call is better served by ``mask=None``;
* the backward uses the same schedules (dQ walks the q-schedule, dK/dV the
  TRANSPOSED schedule: per kv-tile, its live q-tiles).

Masking inside a live-but-partial tile is in-register via the mask's
``apply``; fully-live tiles skip it (``full`` flag per schedule slot).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ray_tpu.ops.flash_attention import (
    _LANE,
    _NEG_INF,
    _block_spec,
    _interpret,
    _scratch,
)

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None


# ------------------------------------------------------------------- masks


class Mask:
    """One head's static sparsity pattern. ``live_tile``/``full_tile`` are
    trace-time (numpy scalars); ``apply`` masks scores in-kernel."""

    def live_tile(self, i: int, j: int, bq: int, bk: int) -> bool:
        raise NotImplementedError

    def full_tile(self, i: int, j: int, bq: int, bk: int) -> bool:
        raise NotImplementedError

    def apply(self, s, rows, cols):
        raise NotImplementedError

    def __eq__(self, other):
        return type(self) is type(other) and vars(self) == vars(other)

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(vars(self).items()))))


class FullMask(Mask):
    """Dense attention (a "global" head in a mixed-head stack)."""

    def live_tile(self, i, j, bq, bk):
        return True

    def full_tile(self, i, j, bq, bk):
        return True

    def apply(self, s, rows, cols):
        return s


class CausalMask(Mask):
    def live_tile(self, i, j, bq, bk):
        return (i + 1) * bq - 1 >= j * bk

    def full_tile(self, i, j, bq, bk):
        # Entire tile below the diagonal: even the first row sees the last col.
        return i * bq >= (j + 1) * bk - 1

    def apply(self, s, rows, cols):
        return jnp.where(rows >= cols, s, _NEG_INF)


class LocalMask(Mask):
    """Sliding-window attention: causal, keeping the last ``window``
    positions per query (Mistral-style local heads)."""

    def __init__(self, window: int):
        self.window = int(window)

    def live_tile(self, i, j, bq, bk):
        causal_live = (i + 1) * bq - 1 >= j * bk
        win_live = (j + 1) * bk - 1 > i * bq - self.window
        return causal_live and win_live

    def full_tile(self, i, j, bq, bk):
        causal_full = i * bq >= (j + 1) * bk - 1
        # Last row's window still covers the tile's first column.
        win_full = ((i + 1) * bq - 1) - j * bk < self.window
        return causal_full and win_full

    def apply(self, s, rows, cols):
        s = jnp.where(rows >= cols, s, _NEG_INF)
        return jnp.where(rows - cols < self.window, s, _NEG_INF)


class ChunkedMask(Mask):
    """Block-diagonal chunks of ``chunk`` positions (chunked prefill /
    local-global stacks): queries attend causally within their chunk."""

    def __init__(self, chunk: int):
        self.chunk = int(chunk)

    def live_tile(self, i, j, bq, bk):
        if not ((i + 1) * bq - 1 >= j * bk):
            return False
        # Any query row sharing a chunk with any kv col in the tile?
        q_chunks = range(i * bq // self.chunk,
                         ((i + 1) * bq - 1) // self.chunk + 1)
        k_chunks = range(j * bk // self.chunk,
                         ((j + 1) * bk - 1) // self.chunk + 1)
        return bool(set(q_chunks) & set(k_chunks))

    def full_tile(self, i, j, bq, bk):
        same_chunk = (i * bq // self.chunk
                      == ((i + 1) * bq - 1) // self.chunk
                      == j * bk // self.chunk
                      == ((j + 1) * bk - 1) // self.chunk)
        return same_chunk and i * bq >= (j + 1) * bk - 1
    def apply(self, s, rows, cols):
        s = jnp.where(rows >= cols, s, _NEG_INF)
        return jnp.where(rows // self.chunk == cols // self.chunk, s,
                         _NEG_INF)


# --------------------------------------------------------------- schedules


class _Schedule:
    """Compacted per-q-tile kv visit lists for one head group (and the
    transpose for the dK/dV pass)."""

    def __init__(self, mask: Mask, nq: int, nk: int, bq: int, bk: int):
        self.mask = mask
        rows: List[List[int]] = []
        fulls: List[List[int]] = []
        live = np.zeros((nq, nk), bool)
        for i in range(nq):
            js = [j for j in range(nk) if mask.live_tile(i, j, bq, bk)]
            if not js:
                js = [0]  # degenerate row: visit one tile, fully masked
            live[i, [j for j in js]] = True
            rows.append(js)
            fulls.append([int(mask.full_tile(i, j, bq, bk)) for j in js])
        self.q_len = max(len(r) for r in rows)
        self.kv_ids = np.zeros((nq, self.q_len), np.int32)
        self.kv_lens = np.asarray([len(r) for r in rows], np.int32)
        self.kv_full = np.zeros((nq, self.q_len), np.int32)
        for i, (js, fl) in enumerate(zip(rows, fulls)):
            self.kv_ids[i, :len(js)] = js
            self.kv_ids[i, len(js):] = js[-1]  # padding refetches last tile
            self.kv_full[i, :len(fl)] = fl
        # Transpose: per kv-tile, its live q-tiles (dK/dV accumulation).
        cols = [[i for i in range(nq) if live[i, j]] or [0]
                for j in range(nk)]
        self.k_len = max(len(c) for c in cols)
        self.q_ids = np.zeros((nk, self.k_len), np.int32)
        self.q_lens = np.asarray([len(c) for c in cols], np.int32)
        for j, is_ in enumerate(cols):
            self.q_ids[j, :len(is_)] = is_
            self.q_ids[j, len(is_):] = is_[-1]
        self.visited = int(self.kv_lens.sum())
        self.total = nq * nk


def _group_heads(masks: Sequence[Mask]) -> List[Tuple[int, int, Mask]]:
    """Consecutive heads sharing a mask -> (start, count, mask) groups."""
    groups = []
    start = 0
    for h in range(1, len(masks) + 1):
        if h == len(masks) or masks[h] != masks[start]:
            groups.append((start, h - start, masks[start]))
            start = h
    return groups


# ----------------------------------------------------------------- kernels


def _sfwd_kernel(kv_ids, kv_lens, kv_full, *refs, scale, bq, bk, mask):
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    i = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = t < kv_lens[i]

    @pl.when(live)
    def _tile():
        j = kv_ids[i, t]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # Partial tiles mask in-register; full tiles skip it (the masked
        # value equals s, selected by where on the prefetched flag).
        s = jnp.where(kv_full[i, t] == 1, s, mask.apply(s, rows, cols))
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
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

    @pl.when(t == nt - 1)
    def _final():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, _NEG_INF,
                        jnp.maximum(m_ref[:, 0:1], _NEG_INF / 2)
                        + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, (bq, _LANE))


def _sched_call(kernel, grid, in_specs, out_specs, out_shape, scratch,
                scalars, args):
    """pallas_call with scalar-prefetch operands (the schedule arrays)."""
    if pltpu is not None:
        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch)
        return pl.pallas_call(kernel, grid_spec=spec, out_shape=out_shape,
                              interpret=_interpret())(*scalars, *args)
    raise RuntimeError("splash schedules need the pallas TPU frontend")


def _sfwd(q, k, v, schedule: _Schedule, scale, bq, bk):
    b, h, sq, d = q.shape
    nq = sq // bq
    grid = (b, h, nq, schedule.q_len)
    group = h // k.shape[1]

    kernel = functools.partial(_sfwd_kernel, scale=scale, bq=bq, bk=bk,
                               mask=schedule.mask)
    # Index maps see the scalar-prefetch refs after the grid indices; the
    # kv block is looked up FROM THE SCHEDULE — this is the compaction.
    in_specs = [
        _block_spec((1, 1, bq, d),
                    lambda b_, h_, i, t, ids, lens, full: (b_, h_, i, 0)),
        _block_spec((1, 1, bk, d),
                    lambda b_, h_, i, t, ids, lens, full:
                    (b_, h_ // group, ids[i, t], 0)),
        _block_spec((1, 1, bk, d),
                    lambda b_, h_, i, t, ids, lens, full:
                    (b_, h_ // group, ids[i, t], 0)),
    ]
    out_specs = [
        _block_spec((1, 1, bq, d),
                    lambda b_, h_, i, t, ids, lens, full: (b_, h_, i, 0)),
        _block_spec((1, 1, bq, _LANE),
                    lambda b_, h_, i, t, ids, lens, full: (b_, h_, i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, _LANE), jnp.float32),
    ]
    scratch = [
        _scratch((bq, d), jnp.float32),
        _scratch((bq, 128), jnp.float32),
        _scratch((bq, 128), jnp.float32),
    ]
    scalars = [jnp.asarray(schedule.kv_ids), jnp.asarray(schedule.kv_lens),
               jnp.asarray(schedule.kv_full)]
    out, lse = _sched_call(kernel, grid, in_specs, out_specs, out_shape,
                           scratch, scalars, [q, k, v])
    return out, lse[..., 0]


def _sbwd_dq_kernel(kv_ids, kv_lens, kv_full, *refs, scale, bq, bk, mask):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
    i = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(t < kv_lens[i])
    def _tile():
        j = kv_ids[i, t]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kv_full[i, t] == 1, s, mask.apply(s, rows, cols))
        p = jnp.exp(s - jnp.maximum(lse, _NEG_INF / 2))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _final():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _sbwd_dkv_kernel(q_ids, q_lens, *refs, scale, bq, bk, mask):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = refs
    j = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(t < q_lens[j])
    def _tile():
        i = q_ids[j, t]
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = mask.apply(s, rows, cols)
        p = jnp.exp(s - jnp.maximum(lse, _NEG_INF / 2))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _final():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _sbwd(q, k, v, out, lse, do, schedule: _Schedule, scale, bq, bk):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    nq, nk = sq // bq, sk // bk

    lse_l = jnp.broadcast_to(lse[..., None], (b, h, sq, _LANE))
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True), (b, h, sq, _LANE))

    def lane(index_map):
        return _block_spec((1, 1, bq, _LANE), index_map)

    # dQ over the forward schedule.
    dq_kernel = functools.partial(_sbwd_dq_kernel, scale=scale, bq=bq,
                                  bk=bk, mask=schedule.mask)
    qmap = lambda b_, h_, i, t, ids, lens, full: (b_, h_, i, 0)  # noqa: E731
    kmap = lambda b_, h_, i, t, ids, lens, full: (  # noqa: E731
        b_, h_ // group, ids[i, t], 0)
    dq = _sched_call(
        dq_kernel, (b, h, nq, schedule.q_len),
        [_block_spec((1, 1, bq, d), qmap),
         _block_spec((1, 1, bk, d), kmap),
         _block_spec((1, 1, bk, d), kmap),
         _block_spec((1, 1, bq, d), qmap),
         lane(qmap), lane(qmap)],
        [_block_spec((1, 1, bq, d), qmap)],
        [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        [_scratch((bq, d), jnp.float32)],
        [jnp.asarray(schedule.kv_ids), jnp.asarray(schedule.kv_lens),
         jnp.asarray(schedule.kv_full)],
        [q, k, v, do, lse_l, delta])[0]

    # dK/dV over the transposed schedule.
    dkv_kernel = functools.partial(_sbwd_dkv_kernel, scale=scale, bq=bq,
                                   bk=bk, mask=schedule.mask)
    qmap2 = lambda b_, h_, j, t, ids, lens: (b_, h_, ids[j, t], 0)  # noqa: E731
    kmap2 = lambda b_, h_, j, t, ids, lens: (b_, h_ // group, j, 0)  # noqa: E731
    dk, dv = _sched_call(
        dkv_kernel, (b, h, nk, schedule.k_len),
        [_block_spec((1, 1, bq, d), qmap2),
         _block_spec((1, 1, bk, d), kmap2),
         _block_spec((1, 1, bk, d), kmap2),
         _block_spec((1, 1, bq, d), qmap2),
         lane(qmap2), lane(qmap2)],
        [_block_spec((1, 1, bk, d),
                     lambda b_, h_, j, t, ids, lens: (b_, h_, j, 0)),
         _block_spec((1, 1, bk, d),
                     lambda b_, h_, j, t, ids, lens: (b_, h_, j, 0))],
        [jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
         jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)],
        [_scratch((bk, d), jnp.float32), _scratch((bk, d), jnp.float32)],
        [jnp.asarray(schedule.q_ids), jnp.asarray(schedule.q_lens)],
        [q, k, v, do, lse_l, delta])
    if group > 1:
        dk = dk.reshape(b, hkv, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, sk, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------- dispatch


def _splash_group(q, k, v, schedule, scale, bq, bk):
    @jax.custom_vjp
    def run(q, k, v):
        return _sfwd(q, k, v, schedule, scale, bq, bk)[0]

    def run_fwd(q, k, v):
        out, lse = _sfwd(q, k, v, schedule, scale, bq, bk)
        return out, (q, k, v, out, lse)

    def run_bwd(res, g):
        q, k, v, out, lse = res
        return _sbwd(q, k, v, out, lse, g, schedule, scale, bq, bk)

    run.defvjp(run_fwd, run_bwd)
    return run(q, k, v)


def splash_attention(
    q: jax.Array,                # (B, S, Hq, D)
    k: jax.Array,                # (B, S, Hkv, D)
    v: jax.Array,                # (B, S, Hkv, D)
    mask: Union[Mask, Sequence[Mask], None] = None,
    causal: bool = True,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Block-sparse attention with per-head static mask schedules.

    ``mask`` is one :class:`Mask` for all heads or a per-head sequence
    (heads with equal masks share one compacted kernel launch — e.g.
    ``[LocalMask(1024)] * 6 + [FullMask()] * 2`` for a local/global
    stack). With ``mask=None`` the causal/window algebra (and data-
    dependent ``segment_ids``) delegates to the shared flash kernel, whose
    flat schedule visits the same tiles; tiles left at ``None`` are then
    that kernel's choice, and 256 for the schedules built here.
    """
    if mask is None:
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, segment_ids=segment_ids,
            kv_segment_ids=kv_segment_ids, block_q=block_q, block_k=block_k,
            scale=scale)
    if segment_ids is not None:
        raise ValueError("segment_ids are data-dependent; use mask=None "
                         "(the flash path) for packed sequences")

    b, sq, hq, d = q.shape
    hkv, sk = k.shape[2], k.shape[1]
    masks = ([mask] * hq if isinstance(mask, Mask) else list(mask))
    if len(masks) != hq:
        raise ValueError(f"{len(masks)} masks for {hq} heads")
    if scale is None:
        scale = d ** -0.5
    bq, bk = min(block_q or 256, sq), min(block_k or 256, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lengths ({sq}, {sk}) must divide blocks "
                         f"({bq}, {bk})")
    group = hq // hkv
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    d_pad = (-d) % _LANE
    if d_pad:
        pad = [(0, 0), (0, 0), (0, 0), (0, d_pad)]
        qt, kt, vt = (jnp.pad(x, pad) for x in (qt, kt, vt))

    outs = []
    for start, count, m in _group_heads(masks):
        if start % group or count % group:
            raise ValueError(
                "per-head masks must align with GQA groups "
                f"(group size {group}); got a boundary at head {start}")
        sched = _Schedule(m, sq // bq, sk // bk, bq, bk)
        outs.append(_splash_group(
            qt[:, start:start + count],
            kt[:, start // group:(start + count) // group],
            vt[:, start // group:(start + count) // group],
            sched, scale, bq, bk))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    if d_pad:
        out = out[..., :d]
    return out.transpose(0, 2, 1, 3)


def schedule_stats(mask: Mask, seq: int, block_q: int = 256,
                   block_k: int = 256) -> dict:
    """Visited vs total tiles for a mask at a given length — the sparsity
    the schedule actually realizes (observability/tests)."""
    s = _Schedule(mask, seq // block_q, seq // block_k, block_q, block_k)
    return {"visited": s.visited, "total": s.total,
            "density": s.visited / s.total, "q_len": s.q_len}
