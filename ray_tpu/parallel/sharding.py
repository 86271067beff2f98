"""Logical-axis sharding rules: how tensors map onto the mesh.

The TPU-native replacement for everything the reference delegates to torch
DDP/FSDP/DeepSpeed (SURVEY §2.4): parameters and activations carry *logical*
axis names (``("vocab", "embed")``), and a rule table maps logical axes to
mesh axes. Changing parallelism strategy = changing the rule table; the model
code never changes (t5x/MaxText-style GSPMD idiom).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicate)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Megatron-style transformer rules. The load-bearing choices:
# * batch over (data, fsdp): gradients psum over both -> plain DP semantics.
# * embed over fsdp: ZeRO-3 — params/optimizer state sharded. XLA gathers
#   a weight where it is used (inside the layer scan, again in the remat
#   backward); a step that accumulates microbatches gathers its bf16
#   compute copy once instead, outside that loop, and pins each gradient
#   to its parameter's shard (train_step.build_train_step; docs/TRAIN.md
#   "What crosses chips in an FSDP step, and when").
# * heads/mlp over tensor: Megatron column->row pairs; XLA inserts the
#   all-reduce at the row-parallel output exactly like hand-written TP.
# * length over seq: context parallelism; attention uses ring_attention
#   (ray_tpu.parallel.ring_attention) so no gather of the full sequence.
# * experts over expert axis: MoE expert sharding, all-to-all routed.
DEFAULT_RULES: Rules = {
    "batch": ("data", "fsdp"),
    "length": "seq",
    "vocab": "tensor",
    "embed": "fsdp",
    # Activations keep the embed dim unsharded (batch already covers fsdp;
    # a duplicate mesh axis in one spec is illegal and embed-sharded
    # activations would force per-op all-to-alls).
    "act_embed": None,
    "heads": "tensor",
    "kv_heads": "tensor",
    "head_dim": None,
    "mlp": "tensor",
    # Pre-contraction anchors: the attention output entering the wo
    # projection and the ffn hidden entering w_down. Under the train rules
    # these equal what propagation already picks (tensor-sharded — the
    # Megatron row-parallel input), so constraining them is free; the
    # DECODE rules map them to None instead, forcing an all-gather BEFORE
    # the contraction so no reduction is ever split across the mesh (the
    # bit-exactness contract of sharded serving).
    "attn_heads": "tensor",
    "mlp_hidden": "tensor",
    "experts": "expert",
    "expert": "expert",      # stacked per-expert weights (MoE)
    "expert_dim": None,      # router output dim (E as a feature axis)
    "layers": None,  # scanned-layer leading axis
    "norm": None,
    "patch": None,   # ViT patch-pixel input axis
}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Rules] = None) -> P:
    """Translate a tuple of logical axis names into a PartitionSpec.
    ``None`` (the whole tuple) means fully replicated."""
    if logical_axes is None:
        return P()
    rules = rules or DEFAULT_RULES
    parts = []
    for ax in logical_axes:
        if ax is None:
            parts.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"no sharding rule for logical axis {ax!r}")
            parts.append(rules[ax])
    # Trim trailing Nones for cleaner specs.
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(mesh: Mesh, axes_tree: Any,
                   rules: Optional[Rules] = None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, spec_for(axes, rules)),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )


def shard_tree(tree: Any, shardings: Any):
    """Device-put a pytree with the given shardings."""
    return jax.tree.map(jax.device_put, tree, shardings)


# Serving (GSPMD model-parallel decode) rules over the 2-axis
# ``decode_mesh`` (("batch", "model"), parallel.mesh.DECODE_AXES). The
# load-bearing difference from DEFAULT_RULES: **no contraction dimension
# is ever partitioned.** Output dims shard (heads/kv_heads/mlp/vocab over
# "model", slots over "batch"); the pre-contraction anchors
# (attn_heads/mlp_hidden) replicate, so XLA inserts all-gathers instead
# of psums and every output element is produced by the exact reduction
# order of the single-chip program — sharded decode logits are BIT-EXACT
# vs the single-chip engine (the serve plane's correctness contract; the
# cost is that wo / w_down stay replicated, see
# ``llama.decode_param_axes``).
DECODE_RULES: Rules = {
    "batch": "batch",
    "length": None,
    "vocab": "model",
    "embed": None,         # contracted by every projection: never shard
    "act_embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "attn_heads": None,    # all-gather before the wo contraction
    "mlp_hidden": None,    # all-gather before the w_down contraction
    "layers": None,
    "norm": None,
    "patch": None,
}


# ZeRO-1 optimizer-state overlay ("Automatic Cross-Replica Sharding of
# Weight Update in Data-Parallel Training", PAPERS.md): optimizer state
# (mu/nu, fp32 master copies) is sharded across the DATA axis as a
# sharding annotation — each replica keeps 1/N of the state, updates its
# shard, and the updated params are all-gathered once per step
# (train_step.build_zero1_train_step pins this with out_shardings).
# The table deliberately uses a STATE-ONLY logical axis name: optimizer
# state is elementwise math, so sharding it can never split a
# reduction — but the moment a MODEL axis name (embed/heads/mlp/...)
# appears here, the same annotations would partition contraction dims
# of the traced step. graftlint's sharding-partitioned-contraction rule
# polices exactly that (ZERO1_STATE_RULES is a bit-exactness table:
# an entry naming an axis that appears in contraction position at any
# einsum/dot site in models/ or parallel/ fails `make lint`).
ZERO1_STATE_RULES: Rules = {
    "zero1_shard": "data",
}


def decode_rules(config, mesh: Mesh) -> Rules:
    """DECODE_RULES specialized to a config + mesh: a dim only shards
    over "model" when its size divides the axis (an indivisible head or
    vocab dim replicates instead of forcing GSPMD's padded sharding —
    padding is correct but wastes the ragged shard's HBM and compute)."""
    model = mesh.shape.get("model", 1)
    rules = dict(DECODE_RULES)
    if model > 1:
        for axis, size in (("heads", config.n_heads),
                           ("kv_heads", config.n_kv_heads),
                           ("mlp", config.mlp_dim),
                           ("vocab", config.vocab_size)):
            if size % model:
                rules[axis] = None
        # GQA reshape constraint: q's heads axis regroups as
        # (kv_heads, groups) inside attention, which only stays a local
        # reshape when the kv split is at least as fine as the head
        # split — otherwise replicate heads with the kv cache.
        if rules["kv_heads"] is None:
            rules["heads"] = None
    return rules


_ctx = threading.local()


@contextmanager
def axis_rules(mesh: Mesh, rules: Optional[Rules] = None):
    """Set the (mesh, rules) context under which ``constrain`` resolves
    logical axes. Train-step builders trace model code inside this context;
    model code stays mesh-agnostic (t5x ``axis_rules`` idiom)."""
    prev = getattr(_ctx, "value", None)
    _ctx.value = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _ctx.value = prev


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]):
    """Apply a GSPMD sharding constraint by logical axis names; no-op when
    no axis_rules context is active (single-device paths, tests).

    A mesh axis that does not divide the tensor's actual dim is dropped
    for that dim (replicate instead): jax rejects uneven shardings
    outright, and the decode plane traces the same constraint
    sites at many batch sizes (admission waves of 1..slots rows) — a
    2-row wave on an 8-way batch axis must replicate, not crash."""
    ctx = getattr(_ctx, "value", None)
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx[0], resolved_spec(x, logical_axes)))


def resolved_spec(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> P:
    """The PartitionSpec ``constrain`` applies to ``x`` under the active
    axis_rules context: the rule table's mesh axes, minus any that do
    not divide the tensor's actual dim."""
    mesh, rules = _ctx.value
    spec = spec_for(logical_axes, rules)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    for i, part in enumerate(parts):
        if part is not None and x.shape[i] % mesh_extent(mesh, part):
            parts[i] = None
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def part_axes(part) -> Tuple[str, ...]:
    """The mesh axes a PartitionSpec entry names (None, a name or a
    tuple of names)."""
    if part is None:
        return ()
    return part if isinstance(part, tuple) else (part,)


def mesh_extent(mesh: Mesh, part) -> int:
    """Devices a PartitionSpec entry (an axis name or a tuple) spans."""
    size = 1
    for ax in part_axes(part):
        size *= mesh.shape.get(ax, 1)
    return size


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_ctx, "value", None)
    return None if ctx is None else ctx[0]


def batch_sharding(mesh: Mesh, rules: Optional[Rules] = None) -> NamedSharding:
    """Sharding for (batch, length, ...) input batches."""
    return NamedSharding(mesh, spec_for(("batch", "length"), rules))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
