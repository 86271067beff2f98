"""Ulysses sequence parallelism: all-to-all head scattering.

The second first-class long-context strategy SURVEY §5.7 demands (next to
ring attention): with the sequence sharded over the ``seq`` mesh axis,
attention needs every query to see every key. Ulysses (DeepSpeed-Ulysses)
converts sequence-sharding into head-sharding for the attention op:

    (B, S/p, H, D) --all_to_all--> (B, S, H/p, D)   heads scattered
        full-sequence attention on H/p local heads
    (B, S, H/p, D) --all_to_all--> (B, S/p, H, D)   back to seq-sharded

Two all-to-alls ride the ICI per layer instead of ring attention's p
ppermute steps; for p <= H it moves strictly less data than an all-gather
of K/V and keeps the attention kernel itself unchanged (so it composes
with the Pallas flash kernel). Reference world: absent from the reference
itself (its role is placement; SURVEY §2.4 SP row) — this is the TPU-native
implementation.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _attention_local(q, k, v, causal: bool, q_offset: int, impl: str):
    if impl == "flash":
        # Off-TPU the kernel runs in Pallas interpret mode
        # (flash_attention._interpret) — the one CPU fallback there is.
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, q_offset=q_offset)
    from ray_tpu.ops.attention import attention

    return attention(q, k, v, causal=causal, q_offset=q_offset)


def ulysses_attention(
    q: jax.Array,  # (B, S, H, D) with S sharded over mesh axis ``seq``
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    causal: bool = True,
    impl: str = "flash",
) -> jax.Array:
    """Exact attention over a sequence-sharded input via head scattering."""
    p = mesh.shape.get(seq_axis, 1)
    if p == 1:
        return _attention_local(q, k, v, causal, 0, impl)
    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_heads % p or n_kv % p:
        raise ValueError(
            f"ulysses needs heads divisible by the seq axis: "
            f"{n_heads}/{n_kv} heads over {p} shards")

    def local(q, k, v):
        # In: (B, S/p, H, D) shards. all_to_all splits the HEAD axis and
        # concatenates the SEQ axis -> (B, S, H/p, D).
        qg = jax.lax.all_to_all(q, seq_axis, split_axis=2, concat_axis=1,
                                tiled=True)
        kg = jax.lax.all_to_all(k, seq_axis, split_axis=2, concat_axis=1,
                                tiled=True)
        vg = jax.lax.all_to_all(v, seq_axis, split_axis=2, concat_axis=1,
                                tiled=True)
        out = _attention_local(qg, kg, vg, causal, 0, impl)
        # Back: split SEQ, concatenate HEADS -> (B, S/p, H, D).
        return jax.lax.all_to_all(out, seq_axis, split_axis=1,
                                  concat_axis=2, tiled=True)

    spec = P(None, seq_axis, None, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
