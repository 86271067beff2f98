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
  accumulates dK/dV (a KV tile stays, query tiles pass), one accumulates dQ
  (a query tile stays, KV tiles pass). ``delta = rowsum(dO * O)`` is a cheap
  elementwise pass left to XLA. The dK/dV kernel works on the TRANSPOSED
  tile (keys on sublanes, queries on lanes), so all four of its matmuls
  are plain ones and ``lse`` / ``delta`` ride as rows, 4 KB a tile.
* The schedule (``_schedule``): causal masking, a sliding ``window`` and
  ``q_offset`` are static, so the tiles a call has to visit are known when
  it is traced. The grid's minor axis walks a FLAT list of exactly those
  tiles (scalar-prefetched tables say which query and KV tile a step is,
  and the index maps read them): a dead tile is neither a grid step nor a
  fetch, so local attention costs O(S * window) steps, not O(S^2). Each
  listed tile is marked as crossed by a band's edge or wholly inside it,
  and only the crossed ones take the body that builds a mask; with
  ``segment_ids`` (data-dependent) every live tile is masked.
  ``schedule_stats`` reports the tiles visited, live and masked a kernel.
* Tile sizes come from the call's shape (``_choose_tiles``): the preferred
  size, found on the chip, cut to what divides the sequence, to
  the window and to a VMEM budget reckoned from the blocks, the scratch and
  the tile's float32 intermediates (``_vmem_bytes``); a call that needs
  more than the default scoped limit asks for it. ``block_q`` /
  ``block_k`` given by the caller are honoured as they are.
* GQA: the KV head for a query head is selected in the BlockSpec index map
  (``h // group``) — the repeat never materializes; the dK/dV kernel walks
  the group's query heads inside one program and writes the group's sum
  once, in the input's dtype.
* ``flash_attention_stats`` returns (out, lse) with a VJP that accepts a
  cotangent for lse (``ds = p * (dp - (delta - g_lse))``) — the hook ring
  attention's cross-shard online-softmax merge differentiates through.

The three ``pallas_call`` names — ``flash_fwd``, ``flash_bwd_dkv``,
``flash_bwd_dq`` — are a contract with the benchmark, which finds the
kernels in a device trace by their HLO instruction names and counts a
step's useful attention work from ``flash_bwd_dq``'s result shape.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANE = 128  # TPU lane width: minor dim of every block must divide into it
_SUBLANE = 8  # second-minor tile of a 32-bit block

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def _interpret() -> bool:
    """Off-TPU (the CPU tests) the kernels run in the Pallas interpreter;
    on the chip they lower through Mosaic (``chip_smoke.py`` checks the
    train step's HLO for the ``tpu_custom_call``)."""
    return jax.default_backend() != "tpu"


def _block_spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# --------------------------------------------------------------- schedule

# A step's flags. FIRST / LAST: the first / last step of its resident tile
# (zero the accumulators / write the result). LIVE: the tile has work;
# MASKED: a band's edge (or segment ids) asks for the in-register mask.
_FIRST, _LAST, _LIVE, _MASKED = 1, 2, 4, 8


class _Schedule(NamedTuple):
    """The flat walk of one kernel: step ``t`` keeps tile ``major[t]``
    resident (a query tile for ``flash_fwd`` / ``flash_bwd_dq``, a KV tile
    for ``flash_bwd_dkv``) and streams tile ``minor[t]`` past it, for query
    head ``head[t]`` of the GQA group (dK/dV only)."""
    major: np.ndarray
    minor: np.ndarray
    head: np.ndarray
    flags: np.ndarray

    @property
    def visited(self) -> int:
        return len(self.flags)

    @property
    def live(self) -> int:
        return int(np.count_nonzero(self.flags & _LIVE))

    @property
    def masked(self) -> int:
        return int(np.count_nonzero(self.flags & _MASKED))

    def tables(self):
        return [jnp.asarray(a) for a in self]


def _tile_bands(sq, sk, block_q, block_k, q_offset, causal, window):
    """(live, inside) over the (query tile, KV tile) plane: does a tile hold
    any unmasked (row, col) of the causal/window bands, and is all of it
    unmasked. Segment masks are data-dependent and never skip tiles."""
    row_min = q_offset + np.arange(sq // block_q)[:, None] * block_q
    row_max = row_min + block_q - 1
    col_min = np.arange(sk // block_k)[None, :] * block_k
    col_max = col_min + block_k - 1
    live = np.ones(np.broadcast_shapes(row_min.shape, col_min.shape), bool)
    inside = live.copy()
    if causal:
        live &= row_max >= col_min
        inside &= row_min >= col_max
    if window is not None:
        # Sliding window keeps cols in (row - window, row].
        live &= col_max > row_min - window
        inside &= row_max - col_min < window
    return live, inside


@functools.lru_cache(maxsize=256)
def _schedule(by_kv: bool, sq: int, sk: int, block_q: int, block_k: int,
              q_offset: int, causal: bool, window: Optional[int],
              segmented: bool, group: int = 1) -> _Schedule:
    """The steps of one kernel, built with numpy when the call is traced.
    A resident tile that no band reaches keeps one step that is not LIVE,
    so that its result is still written (zeros)."""
    live, inside = _tile_bands(sq, sk, block_q, block_k, q_offset, causal,
                               window)
    masked = live & (~inside | segmented)
    if by_kv:
        live, masked = live.T, masked.T
    major, minor, head, flags = [], [], [], []
    for a in range(live.shape[0]):
        passing = np.flatnonzero(live[a])
        steps = [(g, b, _LIVE | (_MASKED if masked[a, b] else 0))
                 for g in range(group if by_kv else 1) for b in passing]
        steps = steps or [(0, 0, 0)]
        for n, (g, b, f) in enumerate(steps):
            major.append(a)
            minor.append(int(b))
            head.append(g)
            flags.append(f | (_FIRST if n == 0 else 0)
                         | (_LAST if n == len(steps) - 1 else 0))
    return _Schedule(*(np.asarray(x, np.int32)
                       for x in (major, minor, head, flags)))


def _for_each_kind(schedule: _Schedule, flags, tile):
    """Runs ``tile(masked)`` under the step's kind, and emits only the
    bodies this schedule has steps for."""
    for masked in (False, True):
        kind = _LIVE | (_MASKED if masked else 0)
        steps = int(np.count_nonzero(
            schedule.flags & (_LIVE | _MASKED) == kind))
        if steps == schedule.visited:
            tile(masked)
        elif steps:
            pl.when(flags & (_LIVE | _MASKED) == kind)(
                functools.partial(tile, masked))


# ------------------------------------------------------------- tile sizes

# What a kernel takes where the shape allows it, (block_q, block_k): the
# sweep on a v5e at q [1,16,4096,128], k/v [1,8,4096,128], bfloat16, causal
# gave all three kernels the same (PERF.md section 5 has the table); what
# they reckon of VMEM differs, so a tight budget can still part them.
_PREFERRED = (1024, 1024)
_VMEM_DEFAULT = 16 * 2 ** 20   # the scoped limit a call gets unasked (v5e)
_VMEM_BUDGET = 64 * 2 ** 20    # what a choice may ask for (of 128 MiB)


def _vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                itemsize: int, segmented: bool) -> int:
    """What a kernel's blocks (double-buffered), scratch and the tile's
    float32 intermediates take of VMEM."""
    lane_col = block_q * _LANE * 4            # a (block_q, LANE) f32 column
    row = _SUBLANE * block_q * 4    # a (1, block_q) f32 row, as VMEM pads it
    q_blk, k_blk = block_q * d * itemsize, block_k * d * itemsize
    seg = (lane_col + _SUBLANE * block_k * 4) if segmented else 0
    if kernel == "flash_fwd":
        blocks = 2 * q_blk + 2 * k_blk + lane_col + seg
        scratch = block_q * d * 4 + 2 * lane_col + q_blk
        tiles = 4
    elif kernel == "flash_bwd_dq":
        blocks = 3 * q_blk + 2 * k_blk + 2 * lane_col + seg
        scratch = block_q * d * 4 + q_blk
        tiles = 5
    else:
        blocks = 2 * q_blk + 4 * k_blk + 2 * row + seg
        scratch = 2 * block_k * d * 4
        tiles = 5
    return 2 * blocks + scratch + tiles * block_q * block_k * 4


def _fit(block: int, seq: int) -> int:
    """The largest halving of ``block`` that divides ``seq`` and is a whole
    number of lanes; ``seq`` itself when it is shorter than one block."""
    block = min(block, seq)
    while block > _LANE and seq % block:
        block //= 2
    return block


def _choose_tiles(kernel: str, sq: int, sk: int, d: int, dtype,
                  window: Optional[int] = None, segmented: bool = False,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None) -> Tuple[int, int]:
    """(block_q, block_k) of one kernel from what the call shows: the
    lengths, the head size, the dtype, a window, segment ids. A size the
    caller gives is taken as it is (cut to the sequence, as before)."""
    want_q, want_k = _PREFERRED
    if window is not None:
        # A tile wider than the window is mostly dead positions.
        cap = max(_LANE, 1 << max(int(window) - 1, 0).bit_length())
        want_q, want_k = min(want_q, cap), min(want_k, cap)
    bq = min(block_q, sq) if block_q else _fit(want_q, sq)
    bk = min(block_k, sk) if block_k else _fit(want_k, sk)
    itemsize = jnp.dtype(dtype).itemsize

    def halves(block, given):
        return not given and block % (2 * _LANE) == 0

    while _vmem_bytes(kernel, bq, bk, d, itemsize, segmented) > _VMEM_BUDGET:
        # Halve the larger side the caller left free, in whole lanes.
        if halves(bk, block_k) and (bk >= bq or not halves(bq, block_q)):
            bk //= 2
        elif halves(bq, block_q):
            bq //= 2
        else:
            break
    if sq % bq or sk % bk:
        raise ValueError(
            f"seq lengths ({sq}, {sk}) must divide blocks ({bq}, {bk})")
    return bq, bk


def _compiler_params(kernel, block_q, block_k, d, dtype, segmented):
    need = _vmem_bytes(kernel, block_q, block_k, d,
                       jnp.dtype(dtype).itemsize, segmented)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need if need > _VMEM_DEFAULT else None)


def schedule_stats(seq_q: int, seq_k: int, head_dim: int = 128,
                   dtype=jnp.bfloat16, causal: bool = True,
                   window: Optional[int] = None, q_offset: int = 0,
                   segmented: bool = False,
                   block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> dict:
    """What each kernel's schedule is for a call of this shape and these
    masks, counted when it would be traced: its tile sizes, the tiles of the
    (query, KV) plane (``total``), the steps a (batch, query head) slice
    takes (``visited``), those with work (``live``), those that build a mask
    (``masked``), and the VMEM it reckons with. ``visited == live`` says no
    dead tile is a grid step."""
    out = {}
    for kernel in KERNELS:
        bq, bk = _choose_tiles(kernel, seq_q, seq_k, head_dim, dtype, window,
                               segmented, block_q, block_k)
        s = _schedule(kernel == "flash_bwd_dkv", seq_q, seq_k, bq, bk,
                      q_offset, causal, window, segmented)
        out[kernel] = {
            "block_q": bq, "block_k": bk,
            "total": (seq_q // bq) * (seq_k // bk),
            "visited": s.visited, "live": s.live, "masked": s.masked,
            "vmem_bytes": _vmem_bytes(kernel, bq, bk, head_dim,
                                      jnp.dtype(dtype).itemsize, segmented),
        }
    return out


# ------------------------------------------------------------------ masks


def _mask_scores(s, i, j, block_q, block_k, q_offset, causal, window,
                 seg_col=None, seg_row=None, q_axis=0):
    """Masks one tile of scores whose queries lie along ``q_axis`` (0: the
    usual (block_q, block_k) tile; 1: dK/dV's transposed one)."""
    rows = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, q_axis)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 - q_axis)
    if causal:
        s = jnp.where(rows >= cols, s, _NEG_INF)
    if window is not None:
        s = jnp.where(rows - cols < window, s, _NEG_INF)
    if seg_col is not None:
        # seg ids ride as fp32 (exact for ids < 2^24), equality only: the
        # sublane side's (block, LANE) read at lane 0, the lane side's
        # (SUBLANE, block) at sublane 0 — the tileable layouts _lane_cols
        # and _sublane_rows build.
        s = jnp.where(seg_col[:, 0:1] == seg_row[0:1, :], s, _NEG_INF)
    return s


def _lane_cols(x):
    """(..., S) -> (..., S, LANE): a value a row, broadcast along lanes so
    that its (block, LANE) blocks are TPU-tileable (readers use lane 0)."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LANE,))


def _sublane_rows(x):
    """(..., S) -> (..., SUBLANE, S): a value a column, broadcast along
    sublanes (readers use sublane 0). A (1, block) block over a (B, S)
    array is refused: second-minor block dim 1 is neither a multiple of 8
    nor B."""
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (_SUBLANE, x.shape[-1]))


# ---------------------------------------------------------------- forward


def _fwd_kernel(major_ref, minor_ref, head_ref, flags_ref, *refs, schedule,
                scale, block_q, block_k, causal, window, q_offset,
                segmented):
    if segmented:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref, qs_ref) = refs
    else:
        (q_ref, k_ref, v_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref, qs_ref) = refs
        sq_ref = sk_ref = None
    t = pl.program_id(2)
    i, j, flags = major_ref[t], minor_ref[t], flags_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # The scale once a query tile, not once a score.
        qs_ref[...] = (q_ref[0, 0].astype(jnp.float32) * scale).astype(
            qs_ref.dtype)

    def _tile(masked):
        k = k_ref[0, 0]  # (block_k, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            qs_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
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

    _for_each_kind(schedule, flags, _tile)

    @pl.when(flags & _LAST != 0)
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


def _call(name, kernel, schedule, grid_heads, in_specs, out_specs, out_shape,
          scratch, params, args):
    """One of the three kernels over its flat schedule. The scope and the
    kernel's name are what a device trace carries: a reader finds the
    kernels by them, not by XLA's numbering."""
    b = args[0].shape[0]
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(b, grid_heads, schedule.visited),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=params,
            interpret=_interpret(),
            name=name,
        )(*schedule.tables(), *args)


def _fwd(q, k, v, seg_q, seg_k, scale, causal, window, q_offset,
         block_q=None, block_k=None):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    segmented = seg_q is not None
    block_q, block_k = _choose_tiles("flash_fwd", sq, sk, d, q.dtype, window,
                                     segmented, block_q, block_k)
    schedule = _schedule(False, sq, sk, block_q, block_k, q_offset, causal,
                         window, segmented)

    kernel = functools.partial(
        _fwd_kernel, schedule=schedule, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, window=window, q_offset=q_offset,
        segmented=segmented)

    def qmap(b_, h_, t, major, minor, head, flags):
        return b_, h_, major[t], 0

    def kmap(b_, h_, t, major, minor, head, flags):
        return b_, h_ // group, minor[t], 0

    in_specs = [
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_k, d), kmap),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            _block_spec((1, block_q, _LANE),
                        lambda b_, h_, t, major, *_: (b_, major[t], 0)),
            _block_spec((1, _SUBLANE, block_k),
                        lambda b_, h_, t, major, minor, *_:
                        (b_, 0, minor[t])),
        ]
        args += [_lane_cols(seg_q), _sublane_rows(seg_k)]
    out, lse = _call(
        "flash_fwd", kernel, schedule, h, in_specs,
        out_specs=[
            _block_spec((1, 1, block_q, d), qmap),
            _block_spec((1, 1, block_q, _LANE), qmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, _LANE), jnp.float32),
        ],
        scratch=[
            _scratch((block_q, d), jnp.float32),
            _scratch((block_q, _LANE), jnp.float32),
            _scratch((block_q, _LANE), jnp.float32),
            _scratch((block_q, d), q.dtype),
        ],
        params=_compiler_params("flash_fwd", block_q, block_k, d, q.dtype,
                                segmented),
        args=args)
    # Keep only lane 0 (the value; other lanes are the tiling broadcast) so
    # the residual saved for the backward is (B, H, S), not 128x that.
    return out, lse[..., 0]


# --------------------------------------------------------------- backward


def _bwd_dkv_kernel(major_ref, minor_ref, head_ref, flags_ref, *refs,
                    schedule, scale, block_q, block_k, causal, window,
                    q_offset, segmented):
    """dK and dV of one KV tile, over every query tile that reaches it of
    every query head in the group. The tile is TRANSPOSED: scores are
    (block_k, block_q), so ``lse`` and ``delta`` are rows."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sk_ref, sq_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        sk_ref = sq_ref = None
    t = pl.program_id(2)
    j, i, flags = major_ref[t], minor_ref[t], flags_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _tile(masked):
        q = q_ref[0, 0]          # (bq, D)
        k = k_ref[0, 0]          # (bk, D)
        v = v_ref[0, 0]
        do = do_ref[0, 0]        # (bq, D)
        lse = lse_ref[0, 0]      # (1, bq)
        delta = delta_ref[0, 0]  # (1, bq)
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bk, bq)
        if masked:
            st = _mask_scores(
                st, i, j, block_q, block_k, q_offset, causal, window,
                None if sk_ref is None else sk_ref[0],
                None if sq_ref is None else sq_ref[0], q_axis=1)
        pt = jnp.exp(st - jnp.maximum(lse, _NEG_INF / 2))
        # dV += P^T dO
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP^T = V dO^T ; dS^T = P^T * (dP^T - delta) * scale
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta) * scale
        # dK += dS^T Q
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_kind(schedule, flags, _tile)

    @pl.when(flags & _LAST != 0)
    def _final():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(major_ref, minor_ref, head_ref, flags_ref, *refs,
                   schedule, scale, block_q, block_k, causal, window,
                   q_offset, segmented):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, dq_acc, qs_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc, qs_ref) = refs
        sq_ref = sk_ref = None
    t = pl.program_id(2)
    i, j, flags = major_ref[t], minor_ref[t], flags_ref[t]

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        qs_ref[...] = (q_ref[0, 0].astype(jnp.float32) * scale).astype(
            qs_ref.dtype)

    def _tile(masked):
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0:1]      # (bq, 1); lane 0 of the columns
        delta = delta_ref[0, 0][:, 0:1]  # (bq, 1)
        s = jax.lax.dot_general(
            qs_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _mask_scores(
                s, i, j, block_q, block_k, q_offset, causal, window,
                None if sq_ref is None else sq_ref[0],
                None if sk_ref is None else sk_ref[0])
        p = jnp.exp(s - jnp.maximum(lse, _NEG_INF / 2))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _for_each_kind(schedule, flags, _tile)

    @pl.when(flags & _LAST != 0)
    def _final():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv(q, k, v, seg_q, seg_k, do, lse, delta, scale, causal, window,
             q_offset, block_q=None, block_k=None):
    """(dk, dv) in the inputs' dtype and KV-head count: one program a
    (batch, KV head) keeps a KV tile and passes the live query tiles of the
    group's query heads by it, so the GQA sum never leaves VMEM."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    segmented = seg_q is not None
    block_q, block_k = _choose_tiles("flash_bwd_dkv", sq, sk, d, q.dtype,
                                     window, segmented, block_q, block_k)
    schedule = _schedule(True, sq, sk, block_q, block_k, q_offset, causal,
                         window, segmented, group)
    kernel = functools.partial(
        _bwd_dkv_kernel, schedule=schedule, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, window=window, q_offset=q_offset,
        segmented=segmented)

    def qmap(b_, g_, t, major, minor, head, flags):
        return b_, g_ * group + head[t], minor[t], 0

    def rowmap(b_, g_, t, major, minor, head, flags):
        return b_, g_ * group + head[t], 0, minor[t]

    def kmap(b_, g_, t, major, minor, head, flags):
        return b_, g_, major[t], 0

    in_specs = [
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, 1, block_q), rowmap),
        _block_spec((1, 1, 1, block_q), rowmap),
    ]
    args = [q, k, v, do, lse[:, :, None, :], delta[:, :, None, :]]
    if segmented:
        in_specs += [
            _block_spec((1, block_k, _LANE),
                        lambda b_, g_, t, major, *_: (b_, major[t], 0)),
            _block_spec((1, _SUBLANE, block_q),
                        lambda b_, g_, t, major, minor, *_:
                        (b_, 0, minor[t])),
        ]
        args += [_lane_cols(seg_k), _sublane_rows(seg_q)]
    dk, dv = _call(
        "flash_bwd_dkv", kernel, schedule, hkv, in_specs,
        out_specs=[
            _block_spec((1, 1, block_k, d), kmap),
            _block_spec((1, 1, block_k, d), kmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, d), v.dtype),
        ],
        scratch=[
            _scratch((block_k, d), jnp.float32),
            _scratch((block_k, d), jnp.float32),
        ],
        params=_compiler_params("flash_bwd_dkv", block_q, block_k, d,
                                q.dtype, segmented),
        args=args)
    return dk, dv


def _bwd_dq(q, k, v, seg_q, seg_k, do, lse, delta, scale, causal, window,
            q_offset, block_q=None, block_k=None):
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    segmented = seg_q is not None
    block_q, block_k = _choose_tiles("flash_bwd_dq", sq, sk, d, q.dtype,
                                     window, segmented, block_q, block_k)
    schedule = _schedule(False, sq, sk, block_q, block_k, q_offset, causal,
                         window, segmented)
    kernel = functools.partial(
        _bwd_dq_kernel, schedule=schedule, scale=scale, block_q=block_q,
        block_k=block_k, causal=causal, window=window, q_offset=q_offset,
        segmented=segmented)

    def qmap(b_, h_, t, major, minor, head, flags):
        return b_, h_, major[t], 0

    def kmap(b_, h_, t, major, minor, head, flags):
        return b_, h_ // group, minor[t], 0

    in_specs = [
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_k, d), kmap),
        _block_spec((1, 1, block_q, d), qmap),
        _block_spec((1, 1, block_q, _LANE), qmap),
        _block_spec((1, 1, block_q, _LANE), qmap),
    ]
    args = [q, k, v, do, _lane_cols(lse), _lane_cols(delta)]
    if segmented:
        in_specs += [
            _block_spec((1, block_q, _LANE),
                        lambda b_, h_, t, major, *_: (b_, major[t], 0)),
            _block_spec((1, _SUBLANE, block_k),
                        lambda b_, h_, t, major, minor, *_:
                        (b_, 0, minor[t])),
        ]
        args += [_lane_cols(seg_q), _sublane_rows(seg_k)]
    return _call(
        "flash_bwd_dq", kernel, schedule, h, in_specs,
        out_specs=[_block_spec((1, 1, block_q, d), qmap)],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)],
        scratch=[_scratch((block_q, d), jnp.float32),
                 _scratch((block_q, d), q.dtype)],
        params=_compiler_params("flash_bwd_dq", block_q, block_k, d, q.dtype,
                                segmented),
        args=args)[0]


def _bwd(q, k, v, seg_q, seg_k, out, lse, do, dlse, scale, causal, window,
         q_offset, block_q, block_k):
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # dS = P * (dP - delta + g_lse): the cotangent of lse rides in delta.
        delta = delta - dlse.astype(jnp.float32)
    masks = (scale, causal, window, q_offset, block_q, block_k)
    dk, dv = _bwd_dkv(q, k, v, seg_q, seg_k, do, lse, delta, *masks)
    dq = _bwd_dq(q, k, v, seg_q, seg_k, do, lse, delta, *masks)
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
                          block_q=None, block_k=None
                          ) -> Tuple[jax.Array, jax.Array]:
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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    segment_ids: Optional[jax.Array] = None,      # (B, S) int
    kv_segment_ids: Optional[jax.Array] = None,   # (B, S_kv) int
) -> jax.Array:
    """Flash attention over (batch, seq, heads, head_dim) tensors.

    Drop-in for ``ray_tpu.ops.attention.attention`` (same signature shape);
    differentiable via the fused Pallas backward. ``window`` keeps only the
    last ``window`` positions per query (sliding-window/local attention —
    dead tiles are not visited, so cost is O(S*window)); ``segment_ids``
    masks cross-segment attention (packed sequences), splash-style.
    ``block_q`` / ``block_k`` left at ``None`` are chosen a kernel from the
    shape (``_choose_tiles``); given, all three kernels take them.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if scale is None:
        scale = d ** -0.5
    # (B, S, H, D) -> (B, H, S, D): puts (S, D) on the (sublane, lane) tile.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # Lane-align head_dim (zero-pad is exact: scores unchanged, padded
    # output columns are sliced off).
    d_pad = (-d) % _LANE
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
