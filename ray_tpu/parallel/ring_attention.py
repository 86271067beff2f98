"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

First-class in this framework where the reference has nothing (SURVEY §5.7:
"absent from the reference — the TPU framework must make this first-class").
Each device holds a contiguous sequence shard of Q, K and V; K/V blocks
rotate around the ring via ``lax.ppermute`` (compiled to ICI neighbor
transfers, which is what the ring layout is *for* — every hop is one ICI
link), and partial attention results merge with the online-softmax
log-sum-exp rule. Attention memory stays O(S_local^2) per device and the
full sequence is never gathered.

Causality comes free from global position offsets: a KV block from a shard
entirely ahead of the local Q shard contributes a fully-masked block (zero
weight), so the math is exact — blocks are not skipped, keeping the loop
shape static for XLA (compute for those blocks is the price of regularity;
a later Pallas kernel can overlap it away with RDMA double-buffering).

Differentiable end-to-end: autodiff of ``ppermute`` produces the reverse
rotation in the backward pass, giving the standard ring-attention backward
schedule without custom VJP code.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import (
    attention_block_stats,
    finalize_attention,
    merge_attention_stats,
)


def ring_attention_local(q, k, v, axis_name: str = "seq",
                         causal: bool = True) -> jax.Array:
    """Per-shard ring attention body; call inside shard_map/pjit-manual.

    Shapes are per-device: q/k/v (B, S_local, H, D) with the global sequence
    laid out contiguously across the ``axis_name`` ring.
    """
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_offset = rank * s_local
    q32 = q.astype(jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def block(acc, m, l, k_cur, v_cur, step):
        src = (rank - step) % n  # origin shard of the K/V block we now hold
        kv_offset = src * s_local
        b_acc, b_m, b_l = attention_block_stats(
            q32, k_cur, v_cur, causal, q_offset, kv_offset)
        return merge_attention_stats(acc, m, l, b_acc, b_m, b_l)

    def body(i, carry):
        acc, m, l, k_cur, v_cur = carry
        # Rotate first (steps 1..n-1), so the final block is not followed by
        # a wasted pair of full-shard ICI transfers.
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        acc, m, l = block(acc, m, l, k_cur, v_cur, i)
        return acc, m, l, k_cur, v_cur

    b, _, h_q, d = q.shape
    acc0 = jnp.zeros((b, h_q, s_local, d), jnp.float32)
    m0 = jnp.full((b, h_q, s_local), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h_q, s_local), jnp.float32)
    acc0, m0, l0 = block(acc0, m0, l0, k, v, 0)  # local block, no transfer
    acc, m, l, _, _ = jax.lax.fori_loop(
        1, n, body, (acc0, m0, l0, k, v))
    return finalize_attention(acc, l, q.dtype)


def _merge_partial(o1, lse1, o2, lse2):
    """Merge two normalized partial attention results by their
    log-sum-exps (blockwise-attention merge rule). Rows dead in both
    partials stay zero."""
    m = jnp.maximum(jnp.maximum(lse1, lse2), -1e30 / 2)
    w1 = jnp.exp(lse1 - m)[..., None]
    w2 = jnp.exp(lse2 - m)[..., None]
    tot = w1 + w2
    o = jnp.where(tot == 0.0, 0.0, (o1 * w1 + o2 * w2) / jnp.where(
        tot == 0.0, 1.0, tot))
    lse = jnp.where(tot[..., 0] == 0.0, -1e30, m + jnp.log(
        jnp.where(tot[..., 0] == 0.0, 1.0, tot[..., 0])))
    return o, lse


def ring_flash_attention_local(q, k, v, axis_name: str = "seq",
                               causal: bool = True,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None) -> jax.Array:
    """Ring attention whose per-hop block compute is the fused Pallas flash
    kernel (``flash_attention_stats``): each hop produces a normalized
    partial (out, lse) for the K/V shard currently held, merged across hops
    with the online-softmax rule. The ``ppermute`` rotation is issued
    before the hop's kernel, so XLA overlaps the ICI transfer of hop i+1
    with the flash compute of hop i (SURVEY §5.7's comm/compute overlap).

    Per-device shapes: q/k/v (B, S_local, H, D), global sequence laid out
    contiguously around the ring. Differentiable: the flash VJP accepts an
    lse cotangent, and ppermute autodiff reverses the rotation. The hops'
    tiles are the kernel's own choice for the shard's length unless given.
    """
    from ray_tpu.ops.flash_attention import flash_attention_stats

    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = d ** -0.5

    # Lane-align head_dim for the kernel (exact: zero-pad).
    d_pad = (-d) % 128
    if d_pad:
        pad = [(0, 0), (0, 0), (0, 0), (0, d_pad)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    d_full = d + d_pad

    # (B, S, H, D) -> (B, H, S, D) once for the whole ring; the rotation
    # ppermutes the transposed K/V directly (layout-agnostic), so no
    # per-hop re-transpose copies.
    qt = q.transpose(0, 2, 1, 3)
    kt0 = k.transpose(0, 2, 1, 3)
    vt0 = v.transpose(0, 2, 1, 3)

    def hop(step, kt, vt):
        """One ring hop. With a contiguous sequence layout the causal mask
        is all-or-nothing at shard granularity for every hop but the local
        one (step 0): kv shard src=(rank-step)%n is fully visible iff
        src < rank, fully masked iff src > rank. The Pallas kernel's
        q_offset must be static, and this decomposition keeps it so — and
        lets lax.cond SKIP masked hops' compute outright (the XLA path pays
        for them; here only the rotation cost remains)."""
        if not causal:
            return flash_attention_stats(qt, kt, vt, scale, False, None, 0,
                                         block_q, block_k)
        if step == 0:
            return flash_attention_stats(qt, kt, vt, scale, True, None, 0,
                                         block_q, block_k)

        def full(ops):
            kt_, vt_ = ops
            return flash_attention_stats(qt, kt_, vt_, scale, False, None,
                                         0, block_q, block_k)

        def dead(ops):
            return (jnp.zeros((b, h, s_local, d_full), q.dtype),
                    jnp.full((b, h, s_local), -1e30, jnp.float32))

        return jax.lax.cond(rank >= step, full, dead, (kt, vt))

    perm = [(j, (j + 1) % n) for j in range(n)]
    o, lse = hop(0, kt0, vt0)
    o = o.astype(jnp.float32)
    k_cur, v_cur = kt0, vt0
    for step in range(1, n):
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        o_h, lse_h = hop(step, k_cur, v_cur)
        o, lse = _merge_partial(o, lse, o_h.astype(jnp.float32), lse_h)
    if d_pad:
        o = o[..., :d]
    return o.astype(q.dtype).transpose(0, 2, 1, 3)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = True,
                   axis_name: str = "seq",
                   batch_axes=("data", "fsdp"),
                   head_axis: Optional[str] = "tensor",
                   impl: str = "xla") -> jax.Array:
    """shard_map wrapper: global (B, S, H, D) arrays sharded batch x seq x
    heads; returns attention output with the same sharding. ``impl="flash"``
    runs each hop through the fused Pallas kernel (tile-skipped causal
    masking + ICI/compute overlap); ``"xla"`` is the portable path."""
    spec = P(batch_axes, axis_name, head_axis, None)
    local = (ring_flash_attention_local if impl == "flash"
             else ring_attention_local)
    kwargs = {}
    if impl == "flash":
        # pallas_call inside shard_map can't declare varying-mesh-axes
        # metadata; skip the replication check for the kernel path.
        kwargs["check_vma"] = False
    fn = shard_map(
        partial(local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        **kwargs,
    )
    return fn(q, k, v)
