"""Llama-family decoder-only transformer, TPU-first.

The flagship model for the framework's training/serving paths (the reference
has no model library — its benchmarks wrap torch models; our north-star is
Llama-2-7B pretraining at >=40% MFU, BASELINE.md). Design choices driven by
the TPU/XLA execution model:

* **Pure functional**: params are a pytree of arrays + a parallel pytree of
  logical axis names (``ray_tpu.parallel.sharding``); one rule table turns
  the same model into DP, FSDP, TP, SP or any mix — no model code changes.
* **Scanned layers**: all decoder layers live in one stacked pytree with a
  leading ``layers`` axis, executed by ``lax.scan`` — one layer is compiled
  once instead of L times (compile time and HLO size stay flat as depth
  grows), and ``jax.checkpoint`` on the scanned body gives the standard
  FSDP-friendly remat schedule.
* **bf16 compute, fp32 accumulation**: matmuls run in bf16 on the MXU with
  fp32 ``preferred_element_type`` where it matters (attention stats, loss);
  master params stay fp32 (cast per step).
* **Static shapes everywhere**; causal masking via position arithmetic so
  ring attention (sequence parallelism) composes by offset, not by mask
  materialization.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import constrain


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    mlp_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Attention implementation: "xla" | "chunked" | "flash" (fused Pallas
    # kernel) | "ring" (requires a seq-sharded mesh context).
    attention_impl: str = "xla"
    remat: bool = True
    # Remat policy: "full" recomputes everything (min memory); "dots" saves
    # matmul outputs and recomputes only elementwise ops; "names" saves the
    # two expensive per-layer intermediates (attention output, ffn hidden)
    # so the backward recomputes only cheap projections/elementwise — the
    # middle point that usually maximizes MFU within HBM on TPU.
    remat_policy: str = "full"
    # Cross-entropy in sequence chunks of this many tokens (0 = whole
    # sequence): avoids materializing the full fp32 (B,S,V) logits, the
    # single largest activation at small model sizes.
    loss_chunk: int = 0
    # Fuse q/k/v into one (E, H+2KV, D) projection and gate/up into one
    # (E, 2M): fewer, larger matmuls — higher MXU utilization on TPU
    # (MaxText-style fused projections).
    fused_qkv: bool = False
    fused_mlp: bool = False
    # Embedding lookup as chunked one-hot MATMULS instead of gather: the
    # gather's backward is a scatter-add over the vocab table, which on a
    # bandwidth-starved part costs ~18% of the whole train step; as matmuls
    # both directions ride the MXU (one-hot chunks are rematerialized in
    # the backward, never stored).
    embed_via_matmul: bool = False
    embed_chunk: int = 512
    # Mixture-of-Experts: replace the dense MLP with moe_experts experts
    # (top-k routing, expert-parallel over the mesh's ``expert`` axis).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        p = self.vocab_size * self.dim  # embed
        mlp_params = 3 * self.dim * self.mlp_dim
        if self.moe_experts:
            mlp_params = (self.moe_experts * 3 * self.dim * self.mlp_dim
                          + self.dim * self.moe_experts)
        per_layer = (
            2 * self.dim  # norms
            + self.dim * self.n_heads * self.head_dim
            + 2 * self.dim * self.n_kv_heads * self.head_dim
            + self.n_heads * self.head_dim * self.dim
            + mlp_params
        )
        p += self.n_layers * per_layer
        p += self.dim  # final norm
        p += self.dim * self.vocab_size  # lm head
        return p


# Reference shapes: Llama-2 family (meta-llama); "debug"/"160m" are test and
# bench scales for single-chip and virtual-mesh runs.
PRESETS: Dict[str, LlamaConfig] = {
    "debug": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, mlp_dim=128, max_seq_len=128),
    "160m": LlamaConfig(vocab_size=32000, dim=768, n_layers=12, n_heads=12,
                        n_kv_heads=12, mlp_dim=2048, max_seq_len=2048),
    "1b": LlamaConfig(vocab_size=32000, dim=2048, n_layers=22, n_heads=16,
                      n_kv_heads=8, mlp_dim=5632, max_seq_len=4096),
    "7b": LlamaConfig(),
    "13b": LlamaConfig(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                       mlp_dim=13824),
    "70b": LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       mlp_dim=28672),
}


def config_for(name_or_config) -> LlamaConfig:
    if isinstance(name_or_config, LlamaConfig):
        return name_or_config
    return PRESETS[name_or_config]


# ------------------------------------------------------------------ params

def param_axes(config: Optional[LlamaConfig] = None) -> Dict[str, Any]:
    """Logical axis names, mirroring the params pytree structure."""
    c = config
    layers: Dict[str, Any] = {
        "attn_norm": ("layers", "embed"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if c is not None and c.fused_qkv:
        layers["wqkv"] = ("layers", "embed", "heads", "head_dim")
    else:
        layers["wq"] = ("layers", "embed", "heads", "head_dim")
        layers["wk"] = ("layers", "embed", "kv_heads", "head_dim")
        layers["wv"] = ("layers", "embed", "kv_heads", "head_dim")
    if c is not None and c.moe_experts:
        layers["moe"] = {
            "router": ("layers", "embed", "expert_dim"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
    elif c is not None and c.fused_mlp:
        layers["w_gate_up"] = ("layers", "embed", "mlp")
    else:
        layers["w_gate"] = ("layers", "embed", "mlp")
        layers["w_up"] = ("layers", "embed", "mlp")
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def decode_param_axes(config: Optional[LlamaConfig] = None) -> Dict[str, Any]:
    """Logical axes for GSPMD *serving* (``sharding.DECODE_RULES``): like
    :func:`param_axes` but the two row-parallel projections — ``wo`` and
    ``w_down`` — are fully replicated. Their input dims are CONTRACTED, so
    sharding them would split a reduction across the mesh and break the
    decode plane's bit-exactness contract; every other projection shards
    an output dim (heads/kv_heads/mlp/vocab over "model") and keeps the
    single-chip reduction order."""
    axes = param_axes(config)
    layers = axes["layers"]
    layers["wo"] = ("layers", None, None, None)
    layers["w_down"] = ("layers", None, None)
    return axes


def init_params(config: LlamaConfig, key: jax.Array,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Initialize master params (fp32 by default). Layer params are stacked
    with a leading ``layers`` axis for lax.scan."""
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    std = 0.02

    def normal(key, shape, fan_in=None):
        scale = std if fan_in is None else (1.0 / math.sqrt(fan_in))
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    lk = jax.random.split(k_layers, 7)
    L, E, H, KV, D, M = (c.n_layers, c.dim, c.n_heads, c.n_kv_heads,
                         c.head_dim, c.mlp_dim)
    layers: Dict[str, Any] = {
        "attn_norm": jnp.ones((L, E), dtype),
        "wo": normal(lk[3], (L, H, D, E), fan_in=H * D),
        "mlp_norm": jnp.ones((L, E), dtype),
        "w_down": normal(lk[6], (L, M, E), fan_in=M),
    }
    if c.fused_qkv:
        layers["wqkv"] = normal(lk[0], (L, E, H + 2 * KV, D), fan_in=E)
    else:
        layers["wq"] = normal(lk[0], (L, E, H, D), fan_in=E)
        layers["wk"] = normal(lk[1], (L, E, KV, D), fan_in=E)
        layers["wv"] = normal(lk[2], (L, E, KV, D), fan_in=E)
    if c.moe_experts:
        nk = jax.random.split(lk[4], 4)
        X = c.moe_experts
        layers["moe"] = {
            "router": normal(nk[0], (L, E, X), fan_in=E),
            "w_gate": normal(nk[1], (L, X, E, M), fan_in=E),
            "w_up": normal(nk[2], (L, X, E, M), fan_in=E),
            "w_down": normal(nk[3], (L, X, M, E), fan_in=M),
        }
    elif c.fused_mlp:
        layers["w_gate_up"] = normal(lk[4], (L, E, 2 * M), fan_in=E)
    else:
        layers["w_gate"] = normal(lk[4], (L, E, M), fan_in=E)
        layers["w_up"] = normal(lk[5], (L, E, M), fan_in=E)
    return {
        "tok_embed": normal(k_embed, (c.vocab_size, E)),
        "layers": layers,
        "final_norm": jnp.ones((E,), dtype),
        "lm_head": normal(k_head, (E, c.vocab_size), fan_in=E),
    }


# ----------------------------------------------------------------- forward

def _decoder_layer(config: LlamaConfig, x, layer, cos, sin, q_offset):
    """One decoder block. ``x``: (B, S, E) in compute dtype."""
    c = config
    h = rms_norm(x, layer["attn_norm"], c.norm_eps)
    h = constrain(h, ("batch", "length", "act_embed"))

    if "wqkv" in layer:
        qkv = jnp.einsum("bse,ehd->bshd", h, layer["wqkv"].astype(h.dtype))
        q = qkv[:, :, :c.n_heads]
        k = qkv[:, :, c.n_heads:c.n_heads + c.n_kv_heads]
        v = qkv[:, :, c.n_heads + c.n_kv_heads:]
    else:
        q = jnp.einsum("bse,ehd->bshd", h, layer["wq"].astype(h.dtype))
        k = jnp.einsum("bse,ehd->bshd", h, layer["wk"].astype(h.dtype))
        v = jnp.einsum("bse,ehd->bshd", h, layer["wv"].astype(h.dtype))
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "length", "heads", "head_dim"))
    k = constrain(k, ("batch", "length", "kv_heads", "head_dim"))

    if c.attention_impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention
        from ray_tpu.parallel.sharding import current_mesh

        mesh = current_mesh()
        if mesh is None:
            raise ValueError("attention_impl='ring' requires an axis_rules "
                             "context with a seq-sharded mesh")
        attn = ring_attention(q, k, v, mesh)
    elif c.attention_impl == "flash":
        attn = _flash_attention(q, k, v, q_offset)
    else:
        attn = attention(q, k, v, causal=True, q_offset=q_offset,
                         impl=c.attention_impl)
    attn = checkpoint_name(attn, "attn_out")
    attn = constrain(attn, ("batch", "length", "attn_heads", "head_dim"))
    out = jnp.einsum("bshd,hde->bse", attn, layer["wo"].astype(h.dtype))
    x = x + constrain(out, ("batch", "length", "act_embed"))

    h2 = rms_norm(x, layer["mlp_norm"], c.norm_eps)
    if "moe" in layer:
        from ray_tpu.ops.moe import moe_ffn

        out, aux = moe_ffn(h2, layer["moe"], top_k=c.moe_top_k,
                           capacity_factor=c.moe_capacity_factor)
        out = constrain(out, ("batch", "length", "act_embed"))
        return x + out, aux
    if "w_gate_up" in layer:
        gate_up = jnp.einsum("bse,em->bsm", h2,
                             layer["w_gate_up"].astype(h2.dtype))
        gate, up = jnp.split(gate_up, 2, axis=-1)
    else:
        gate = jnp.einsum("bse,em->bsm", h2,
                          layer["w_gate"].astype(h2.dtype))
        up = jnp.einsum("bse,em->bsm", h2, layer["w_up"].astype(h2.dtype))
    ffn = jax.nn.silu(gate) * up
    ffn = checkpoint_name(ffn, "mlp_hidden")
    ffn = constrain(ffn, ("batch", "length", "mlp_hidden"))
    down = jnp.einsum("bsm,me->bse", ffn, layer["w_down"].astype(h2.dtype))
    return x + constrain(down, ("batch", "length", "act_embed")), jnp.zeros(
        (), jnp.float32)


def _flash_attention(q, k, v, q_offset):
    """Pallas flash attention. Under a multi-device ``axis_rules`` context
    the kernel runs inside ``shard_map`` on each device's (batch, heads)
    shard: GSPMD cannot partition a Mosaic kernel (lowering raises
    "Mosaic kernels cannot be automatically partitioned")."""
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.sharding import (mesh_extent, current_mesh,
                                           resolved_spec)

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, q_offset=q_offset)

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(q, k, v)
    q_spec = list(resolved_spec(q, ("batch", "length", "heads"))) + [None] * 3
    kv_spec = list(resolved_spec(k, ("batch", "length", "kv_heads"))) \
        + [None] * 3
    if q_spec[1] is not None and mesh_extent(mesh, q_spec[1]) > 1:
        raise ValueError(
            "attention_impl='flash' needs each device to hold whole "
            "sequences; on a seq-sharded mesh use attention_impl='ring'")
    if q_spec[2] != kv_spec[2]:
        # GQA maps query head h to kv head h // group: only a split that
        # cuts both head axes the same way keeps that mapping local.
        q_spec[2] = kv_spec[2] = None
    q_spec, kv_spec = P(*q_spec[:3]), P(*kv_spec[:3])
    return jax.shard_map(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def _embed_matmul(table: jax.Array, tokens: jax.Array,
                  chunk: int = 512) -> jax.Array:
    """Embedding gather expressed as chunked one-hot matmuls (see
    ``embed_via_matmul``). Each chunk's one-hot is built, multiplied, and
    (via checkpoint) rebuilt in the backward — the vocab-table gradient
    becomes ``one_hot^T @ dy`` matmuls instead of a scatter-add."""
    b, s = tokens.shape
    v, e = table.shape
    flat = tokens.reshape(-1)
    n = flat.shape[0]
    chunk = min(chunk, n)
    if n % chunk:
        # Largest divisor of n that fits the requested chunk: keeps the
        # one-hot buffer bounded for ANY (B, S) instead of silently
        # collapsing to a single n-sized chunk (a 3 GB one-hot at bench
        # scales).
        chunk = next(c for c in range(chunk, 0, -1) if n % c == 0)

    # A chunk's columns are cut as the table's own ("embed"): against a
    # table sharded so the partitioner picks this itself; against a table
    # that every device holds whole (a hoisted compute copy) it would
    # otherwise have every device compute every chunk, whole.
    @jax.checkpoint
    def one_chunk(tok_c):
        onehot = jax.nn.one_hot(tok_c, v, dtype=table.dtype)
        return constrain(onehot @ table, (None, "embed"))

    def body(_, tok_c):
        return None, one_chunk(tok_c)

    _, out = jax.lax.scan(body, None, flat.reshape(n // chunk, chunk))
    return out.reshape(b, s, e)


def remat_wrap(body, config: LlamaConfig):
    """Apply the config's remat policy to a scan body (shared by the full
    model and pipeline stages so policies never diverge)."""
    policy = None
    if config.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif config.remat_policy == "names":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_hidden")
    return jax.checkpoint(body, prevent_cse=False, policy=policy)


def hidden_states(params: Dict[str, Any], tokens: jax.Array,
                  config: LlamaConfig) -> jax.Array:
    """Token ids (B, S) -> final-norm hidden states (B, S, E)."""
    c = config
    if c.embed_via_matmul:
        x = _embed_matmul(params["tok_embed"].astype(c.dtype), tokens,
                          chunk=c.embed_chunk)
    else:
        # All-gather the table BEFORE the lookup: left to itself XLA
        # gathers from the fsdp-sharded table and then cannot convert the
        # embed-sharded output to batch sharding on permuted-order meshes
        # (expert/dcn/multi-process) — spmd_partitioner falls back to
        # "Involuntary full rematerialization", replicating the whole
        # activation every step. One explicit table all-gather is the
        # cheap, local-lookup form of the same data movement.
        table = constrain(params["tok_embed"].astype(c.dtype),
                          (None, None))
        x = table[tokens]
    x = constrain(x, ("batch", "length", "act_embed"))
    cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)

    def body(carry, layer):
        x, aux_sum = carry
        x, aux = _decoder_layer(c, x, layer, cos, sin, 0)
        return (x, aux_sum + aux), None

    if c.remat:
        body = remat_wrap(body, c)
    (x, aux_sum), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    return rms_norm(x, params["final_norm"], c.norm_eps), aux_sum


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: LlamaConfig) -> jax.Array:
    """Token ids (B, S) -> logits (B, S, V) in fp32."""
    c = config
    x, _aux = hidden_states(params, tokens, config)
    logits = jnp.einsum("bse,ev->bsv", x,
                        params["lm_head"].astype(c.dtype),
                        preferred_element_type=jnp.float32)
    return constrain(logits, ("batch", "length", "vocab"))


def _chunk_ce(x_c, targets_c, lm_head):
    """Cross entropy for one sequence chunk; logits never leave the chunk."""
    logits = jnp.einsum("bse,ev->bsv", x_c, lm_head,
                        preferred_element_type=jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets_c[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def _chunks(x, targets, chunk):
    """(B, S, ...) -> (S / chunk, B, chunk, ...): the scan's leading axis."""
    b, s = targets.shape
    n = s // chunk
    return (x.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3),
            targets.reshape(b, n, chunk).transpose(1, 0, 2))


def _ce_sum(x, targets, lm_head, chunk):
    """Cross entropy summed over all tokens, the head matmul + CE run per
    sequence chunk under remat."""

    def body(total, xt):
        x_c, t_c = xt
        return total + jax.checkpoint(_chunk_ce)(x_c, t_c, lm_head), None

    chunks = _chunks(x, targets, chunk)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), chunks)
    return total


def _ce_sum_batch_sharded(x, targets, lm_head, chunk):
    """:func:`_ce_sum` on a mesh that cuts the batch: each device runs the
    chunk loop over its own sequences against the whole head, and the
    head's gradient crosses the chips ONCE, after the loop, as a
    reduce-scatter into the head's own shard. Left to the partitioner the
    sum over the batch's shards is taken inside the loop, where the
    gradient is accumulated: an all-reduce of the WHOLE head every chunk
    (16 a step of 379 MB at internlm2-1.8b on four chips). Falls back to
    :func:`_ce_sum` where no mesh is active, the batch is not cut, or
    the mesh cuts the model too."""
    from ray_tpu.parallel.sharding import (current_mesh, mesh_extent,
                                           part_axes, resolved_spec)

    mesh = current_mesh()
    if mesh is None:
        return _ce_sum(x, targets, lm_head, chunk)
    batch_axes = tuple(a for part in resolved_spec(x, ("batch",))
                       for a in part_axes(part))
    if mesh_extent(mesh, batch_axes) == 1:
        return _ce_sum(x, targets, lm_head, chunk)
    if any(size > 1 for axis, size in mesh.shape.items()
           if axis not in batch_axes):
        # A mesh that also cuts the model (tensor, seq, expert) keeps the
        # partitioner's program: the loop below would have to be manual
        # over the batch's axes only, and XLA's CPU backend dies on a
        # bfloat16 reduce-scatter there ("Invalid binary instruction
        # opcode copy", jax 0.9).
        return _ce_sum(x, targets, lm_head, chunk)
    # The head's own dim that an axis of the batch also cuts (embed over
    # fsdp): the gradient is scattered there; over the batch's other axes
    # (data) it is summed.
    scatter_dim, scatter_axes = 0, ()
    for dim, part in enumerate(resolved_spec(lm_head, ("embed", "vocab"))):
        axes = tuple(a for a in part_axes(part) if a in batch_axes)
        if axes:
            scatter_dim, scatter_axes = dim, axes
            break
    sum_axes = tuple(a for a in batch_axes if a not in scatter_axes)
    rows = P(batch_axes)
    grad_spec = P(*([None] * scatter_dim + [scatter_axes])) \
        if scatter_axes else P()

    def local(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def forward(x, targets, lm_head):
        return jax.lax.psum(_ce_sum(x, targets, lm_head, chunk), batch_axes)

    def backward(ct, x, targets, lm_head):
        def body(d_head, xt):
            x_c, t_c = xt
            _, vjp = jax.vjp(lambda x_c, w: _chunk_ce(x_c, t_c, w),
                             x_c, lm_head)
            dx_c, d_head_c = vjp(ct)
            return d_head + d_head_c, dx_c

        d_head, dx = jax.lax.scan(body, jnp.zeros_like(lm_head),
                                  _chunks(x, targets, chunk))
        if scatter_axes:
            d_head = jax.lax.psum_scatter(
                d_head, scatter_axes, scatter_dimension=scatter_dim,
                tiled=True)
        if sum_axes:
            d_head = jax.lax.psum(d_head, sum_axes)
        # The barrier keeps the reduction HERE. A caller that sums this
        # gradient over a loop of microbatches would otherwise see XLA
        # move the collective behind its loop and carry the whole
        # unreduced float32 gradient through it (758 MB a chip at
        # internlm2-1.8b's head, where 14.8 of 15.75 GB are spoken for).
        d_head = jax.lax.optimization_barrier(d_head)
        return dx.transpose(1, 0, 2, 3).reshape(x.shape), d_head

    @jax.custom_vjp
    def ce_sum(x, targets, lm_head):
        return local(forward, (rows, rows, P()), P())(x, targets, lm_head)

    def ce_sum_fwd(x, targets, lm_head):
        return ce_sum(x, targets, lm_head), (x, targets, lm_head)

    def ce_sum_bwd(saved, ct):
        dx, d_head = local(backward, (P(), rows, rows, P()),
                           (rows, grad_spec))(ct, *saved)
        return dx, None, d_head

    ce_sum.defvjp(ce_sum_fwd, ce_sum_bwd)
    return ce_sum(x, targets, lm_head)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            config: LlamaConfig) -> jax.Array:
    """Next-token cross entropy. ``batch``: {"tokens": (B, S+1) int32} or
    {"inputs": (B, S), "targets": (B, S)}; fp32 log-softmax. With
    ``config.loss_chunk`` the (B,S,V) fp32 logits are never materialized —
    the head matmul + CE run per sequence chunk under remat."""
    c = config
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    x, aux = hidden_states(params, inputs, c)
    lm_head = params["lm_head"].astype(c.dtype)
    b, s, _ = x.shape
    chunk = c.loss_chunk
    if chunk and s % chunk == 0 and s > chunk:
        loss = _ce_sum_batch_sharded(x, targets, lm_head, chunk) / (b * s)
        if c.moe_experts:
            loss = loss + c.moe_aux_coef * aux / c.n_layers
        return loss
    logits = jnp.einsum("bse,ev->bsv", x, lm_head,
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, ("batch", "length", "vocab"))
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - gold)
    if c.moe_experts:
        loss = loss + c.moe_aux_coef * aux / c.n_layers
    return loss


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token (fwd+bwd ~= 6*N plus attention quadratic term)."""
    c = config
    param_flops = 6.0 * c.num_params()
    # attention scores+values: 2 matmuls * 2 (fwd) * 3 (fwd+bwd) per token:
    attn_flops = 12.0 * c.n_layers * c.n_heads * c.head_dim * seq_len
    return param_flops + attn_flops
