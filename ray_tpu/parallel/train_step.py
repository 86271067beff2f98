"""SPMD train-step builder: model + optimizer + mesh -> one compiled step.

This is the compute core of the JaxTrainer (the reference's equivalent layer
is Train's per-worker torch train loop + DDP/NCCL, ``train/torch/config.py``;
here the entire parallelism stack — DP/FSDP/TP/SP — is inside one jitted
function and XLA inserts the collectives). The builder:

1. materializes params *directly sharded* (jit init with out_shardings — no
   host-side full copy, which matters at 7B+),
2. derives optimizer-state shardings by propagation (jit of optimizer.init
   over committed-sharded params),
3. returns a donated, jitted ``step(params, opt_state, batch)`` whose body
   runs under the mesh's ``axis_rules`` so the model's ``constrain`` calls
   resolve.
"""

from __future__ import annotations

import math
import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import (
    ZERO1_STATE_RULES,
    Rules,
    axis_rules,
    batch_sharding,
    part_axes,
    spec_for,
    tree_shardings,
)
from ray_tpu.util import flightrec


def init_sharded_params(init_fn: Callable[[jax.Array], Any],
                        axes_tree: Any, mesh: Mesh, key: jax.Array,
                        rules: Optional[Rules] = None):
    """Run ``init_fn(key)`` with outputs materialized under the mesh's param
    shardings — each device only ever holds its shard."""
    t0 = time.time()
    shardings = tree_shardings(mesh, axes_tree, rules)
    with jax.transfer_guard("allow"):
        init = jax.jit(init_fn, out_shardings=shardings)
        params = init(key)
    _record_weights(t0, params)
    return params


def _record_weights(t0: float, tree) -> None:
    """The set-up record's ``weights`` phase: the host's time since ``t0``
    to build and dispatch ``tree``'s initialisation (the device may still
    be filling it), and the bytes it will hold."""
    flightrec.record("setup.phase", phase="weights", t0=t0, t1=time.time(),
                     bytes=sum(x.nbytes for x in jax.tree.leaves(tree)))


def init_optimizer_state(optimizer: optax.GradientTransformation, params):
    """optimizer.init jitted with every params-shaped subtree of the state
    (adam's mu/nu, momentum, ...) pinned to the params' own shardings and
    the rest (step counts) replicated — ZeRO optimizer-state sharding
    without a separate code path. The shardings must be given: the state
    is built from ``zeros_like``, which depends on no input data, so XLA
    has nothing to propagate from and an unpinned jit leaves the WHOLE
    state on device 0 (seen on a four-chip v5e: device-0 peak 16.4 GB for
    a 1.2B model, 3.5 GB on the others)."""
    t0 = time.time()
    state = _optimizer_init(optimizer, params)(params)
    _record_weights(t0, state)
    return state


def _optimizer_init(optimizer: optax.GradientTransformation, params):
    sharding = jax.tree.leaves(params)[0].sharding
    if not isinstance(sharding, NamedSharding):
        return jax.jit(optimizer.init)
    param_shardings = jax.tree.map(lambda p: p.sharding, params)
    params_def = jax.tree.structure(params)
    replicated_sh = NamedSharding(sharding.mesh, P())

    def like_params(node) -> bool:
        return jax.tree.structure(node) == params_def

    shardings = jax.tree.map(
        lambda node: param_shardings if like_params(node) else replicated_sh,
        jax.eval_shape(optimizer.init, params), is_leaf=like_params)
    return jax.jit(optimizer.init, out_shardings=shardings)


def _without(spec: P, axes) -> P:
    """``spec`` with the mesh axes in ``axes`` taken out of every entry."""
    parts = []
    for part in spec:
        kept = tuple(a for a in part_axes(part) if a not in axes)
        parts.append(kept[0] if len(kept) == 1 else (kept or None))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _pin(tree, shardings):
    """``tree`` with each leaf constrained to its entry of the flat
    ``shardings`` (``None`` = leave the leaf to the partitioner)."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef.unflatten([
        x if sh is None else jax.lax.with_sharding_constraint(x, sh)
        for x, sh in zip(leaves, shardings)])


def _shard_bytes(x, sharding=None, itemsize=None) -> int:
    """Bytes of ``x`` that one device holds under ``sharding`` (default:
    its own)."""
    sh = getattr(x, "sharding", None) if sharding is None else sharding
    shape = sh.shard_shape(x.shape) if hasattr(sh, "shard_shape") else x.shape
    return math.prod(shape) * (itemsize or jnp.dtype(x.dtype).itemsize)


def _bytes_limit(device) -> Optional[int]:
    """What the runtime lets a program hold on ``device``; None where it
    does not say (the CPU, a chip that is described and not attached)."""
    try:
        return (device.memory_stats() or {}).get("bytes_limit")
    except Exception:
        return None


class _TrainStep:
    """What :func:`build_train_step` returns: the jitted step, called and
    lowered like one. It reads the layout of the arguments it is handed
    (their ``NamedSharding``s, concrete arrays or shape structs alike) and
    passes it to the program as a static argument, so the step body can
    say where a gradient lands and what the microbatch loop closes over
    without the caller naming it."""

    def __init__(self, jitted, batch_axes, accumulates: bool):
        self._jitted, self._batch_axes = jitted, batch_axes
        self._accumulates = accumulates
        self._layouts: Dict[Tuple, Tuple] = {}

    def _layout(self, params, opt_state, batch):
        """Per leaf of ``params``: where its gradient is pinned (its own
        sharding) and where its compute copy is (the same less the axes
        the batch is cut over); ``None`` for a leaf that is replicated,
        which says nothing about either (ZeRO-1's params, one device).
        The copies are whole only if they fit the device beside what the
        step holds anyway; else every weight stays a shard and is gathered
        where it is used, as the partitioner places it."""
        leaves = jax.tree.leaves(params)
        key = tuple(getattr(leaf, "sharding", None) for leaf in leaves)
        if key not in self._layouts:
            grads, copies = [], []
            for sh in key:
                cut = (isinstance(sh, NamedSharding)
                       and not sh.is_fully_replicated)
                whole = NamedSharding(sh.mesh, _without(
                    sh.spec, self._batch_axes)) if cut else None
                grads.append(sh if cut else None)
                copies.append(None if whole == sh else whole)
            if not (self._accumulates and any(copies) and self._fits(
                    (params, opt_state, batch), leaves, grads, copies)):
                copies = [None] * len(leaves)
            self._layouts[key] = tuple(grads), tuple(copies)
        return self._layouts[key]

    @staticmethod
    def _fits(arguments, leaves, grads, copies) -> bool:
        """Whether one device can hold the step's arguments, the float32
        accumulator and the bfloat16 copy with ``copies`` whole; True
        where the device does not say what it can hold."""
        mesh = next(sh for sh in grads if sh is not None).mesh
        limit = _bytes_limit(mesh.devices.flat[0])
        if limit is None:
            return True
        held = sum(_shard_bytes(x) for x in jax.tree.leaves(arguments))
        for leaf, sh, whole in zip(leaves, grads, copies):
            held += _shard_bytes(leaf, sh, 4) + _shard_bytes(
                leaf, whole or sh, 2)
        return held <= limit

    def __call__(self, params, opt_state, batch):
        return self._jitted(self._layout(params, opt_state, batch), params,
                            opt_state, batch)

    def lower(self, params, opt_state, batch):
        return self._jitted.lower(self._layout(params, opt_state, batch),
                                  params, opt_state, batch)


def build_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    rules: Optional[Rules] = None,
    extra_metrics: Optional[Callable] = None,
    accum_steps: int = 1,
    out_shardings=None,
):
    """Returns ``step(params, opt_state, batch) -> (params, opt_state,
    metrics)``, jitted with donated state (call it, or ``.lower(...)`` it).
    ``out_shardings`` (a ``(params, opt_state, metrics)`` sharding triple,
    None = let XLA propagate) is how :func:`build_zero1_train_step` pins
    the ZeRO-1 layout without a second step body.

    ``accum_steps > 1`` splits the batch's leading axis into that many
    microbatches and accumulates fp32 gradients over a ``lax.scan`` before
    ONE optimizer update, so the (bandwidth-bound on TPU) optimizer pass
    amortizes over ``accum_steps`` times more tokens.

    What crosses chips follows the layout of the ``params`` the step is
    handed (docs/TRAIN.md "What crosses chips in an FSDP step, and
    when"). A gradient is pinned to its parameter's sharding, so the sum
    over the batch's shards lands in the shard that accumulates it and
    the accumulator is a shard, never a whole gradient. The bf16
    compute copy is made ONCE a step, outside the microbatch loop, and
    gathered there over the mesh axes the batch is also cut over (fsdp):
    the loop closes over whole weights and moves none. That copy costs 2
    bytes a parameter on every chip for the length of the step.

    On a multi-chip mesh keep ``batch_size / accum_steps`` a multiple of
    the batch-sharding mesh extent (data x fsdp), or XLA resorts to
    replicate-then-reshard on every microbatch slice."""
    batch_axes = frozenset(
        a for part in spec_for(("batch",), rules) for a in part_axes(part))

    def _grads_accum(params, batch, layout):
        grad_sh, copy_sh = layout
        pbf = _pin(jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params), copy_sh)

        def micro(g_acc, mb):
            loss, g = jax.value_and_grad(loss_fn)(pbf, mb)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                 g_acc, _pin(g, grad_sh))
            return g_acc, loss

        mbs = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                + x.shape[1:]), batch)
        g0 = _pin(jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params), grad_sh)
        grads, losses = jax.lax.scan(micro, g0, mbs)
        grads = jax.tree.map(lambda g: g / accum_steps, grads)
        return losses.mean(), grads

    # Jitted under its own name: the program is ``train_step`` in a
    # profiler trace and in the lowered module, whatever builds it.
    def train_step(layout, params, opt_state, batch):
        with axis_rules(mesh, rules):
            if accum_steps > 1:
                loss, grads = _grads_accum(params, batch, layout)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                grads = _pin(grads, layout[0])
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            metrics = {"loss": loss,
                       "grad_norm": optax.global_norm(grads)}
            if extra_metrics is not None:
                metrics.update(extra_metrics(new_params, batch))
        return new_params, new_opt_state, metrics

    pins = {} if out_shardings is None else {"out_shardings": out_shardings}
    return _TrainStep(jax.jit(train_step, static_argnums=0,
                              donate_argnums=(1, 2), **pins), batch_axes,
                      accumulates=accum_steps > 1)


# --------------------------------------------------------------- ZeRO-1
#
# Cross-replica sharded weight update ("Automatic Cross-Replica Sharding
# of Weight Update in Data-Parallel Training", PAPERS.md) expressed as
# sharding ANNOTATIONS on the optimizer state: params stay replicated
# (plain DP semantics, every replica sees the full model), while mu/nu
# (and any fp32 master copies optax keeps) shard 1/N over the data axis.
# XLA reads the annotations and compiles the weight update into
# reduce-scatter(grads) -> per-shard elementwise update -> all-gather
# (params), run ONCE per step — the update's memory AND flops drop to
# 1/N per replica with zero model-code changes. The mesh axis the state
# shards over comes from ``sharding.ZERO1_STATE_RULES`` (a rule-table
# annotation graftlint polices: a table edit that would partition a
# contraction dim of the traced step fails ``make lint``).


def zero1_state_shardings(mesh: Mesh, opt_state: Any,
                          zero1_rules: Optional[Rules] = None):
    """NamedShardings for an optimizer-state pytree: each array leaf
    shards its FIRST axis-divisible dim over the ZeRO-1 mesh axis; leaves
    with no divisible dim (scalars like adam's ``count``, tiny norms)
    replicate — jax rejects uneven shardings, and a ragged shard
    would waste the padding anyway. Works on concrete arrays or
    ``jax.eval_shape`` structs.

    ``zero1_rules`` is the ZeRO-1 STATE table (default
    :data:`~ray_tpu.parallel.sharding.ZERO1_STATE_RULES`), not the
    model-axis rules table — a table without the ``zero1_shard`` key is
    rejected rather than silently replicating the state."""
    table = zero1_rules or ZERO1_STATE_RULES
    if "zero1_shard" not in table:
        raise ValueError(
            "ZeRO-1 state table has no 'zero1_shard' key — this looks "
            "like a model-axis rules table passed where the state "
            "table belongs (the state would silently replicate); pass "
            "it as rules=, and the state table as zero1_rules=")
    mesh_ax = table.get("zero1_shard")
    n = mesh.shape.get(mesh_ax, 1) if isinstance(mesh_ax, str) else 1
    replicated_sh = NamedSharding(mesh, P())

    def leaf_sharding(x):
        shape = getattr(x, "shape", ())
        if n > 1:
            for dim, size in enumerate(shape):
                if size >= n and size % n == 0:
                    return NamedSharding(
                        mesh, P(*([None] * dim + [mesh_ax])))
        return replicated_sh

    return jax.tree.map(leaf_sharding, opt_state)


def init_zero1_opt_state(optimizer: optax.GradientTransformation, params,
                         mesh: Mesh,
                         zero1_rules: Optional[Rules] = None):
    """``optimizer.init`` jitted with ZeRO-1 out_shardings: every state
    leaf materializes already sharded over the data axis — no replica
    ever holds the full optimizer state."""
    state_shape = jax.eval_shape(optimizer.init, params)
    shardings = zero1_state_shardings(mesh, state_shape, zero1_rules)
    with jax.transfer_guard("allow"):
        return jax.jit(optimizer.init, out_shardings=shardings)(params)


def build_zero1_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params,
    rules: Optional[Rules] = None,
    zero1_rules: Optional[Rules] = None,
    extra_metrics: Optional[Callable] = None,
    accum_steps: int = 1,
):
    """ZeRO-1 twin of :func:`build_train_step`: same step body, but the
    jit pins out_shardings — params REPLICATED (the once-per-step
    all-gather of the updated weights), optimizer state sharded per
    :func:`zero1_state_shardings`. ``params`` is only inspected for
    structure (``jax.eval_shape``); pass the live pytree.

    ``rules`` and ``zero1_rules`` are DISTINCT namespaces: ``rules`` is
    the model-axis table the step body runs under (resolving the
    model's ``constrain`` calls, like :func:`build_train_step`),
    ``zero1_rules`` is the ZeRO-1 state table (default
    ``ZERO1_STATE_RULES``). A single parameter used to feed both, so
    any non-None value silently broke one of the two uses — most
    treacherously, a model table made ``zero1_shard`` miss and the
    state replicated with no error."""
    state_shape = jax.eval_shape(optimizer.init, params)
    opt_shardings = zero1_state_shardings(mesh, state_shape, zero1_rules)
    replicated_sh = NamedSharding(mesh, P())
    param_shardings = jax.tree.map(lambda _: replicated_sh, params)
    step = build_train_step(
        loss_fn, optimizer, mesh, rules=rules,
        extra_metrics=extra_metrics, accum_steps=accum_steps,
        out_shardings=(param_shardings, opt_shardings, None))

    def traced_step(params, opt_state, batch):
        """One span per ZeRO-1 step when a trace is active (the
        params all-gather is the out_shardings pin INSIDE the jitted
        program, so the span covers update+gather as one unit —
        `ray_tpu timeline --train` renders it on the learner's row).
        Untraced callers pay one contextvar read."""
        from ray_tpu.util import tracing

        if not tracing.traced():
            return step(params, opt_state, batch)
        import time as _time

        t0 = _time.time()
        out = step(params, opt_state, batch)
        jax.block_until_ready(out[0])
        tracing.record_span("zero1:step", t0, _time.time(),
                            allgather="params", zero1=True)
        return out

    return traced_step


def per_replica_state_bytes(opt_state) -> int:
    """The WORST replica's resident optimizer-state bytes: per device,
    the sum of that device's addressable shard bytes across every state
    leaf (a replicated leaf charges its full size to every device; a
    ZeRO-1 leaf charges 1/N). The ZeRO-1 acceptance asserts this lands
    at ~1/N of the unsharded total."""
    per_device: Dict[Any, int] = {}
    for leaf in jax.tree.leaves(opt_state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        for shard in leaf.addressable_shards:
            per_device[shard.device] = (per_device.get(shard.device, 0)
                                        + shard.data.nbytes)
    return max(per_device.values()) if per_device else 0


# What crosses chips in a compiled step: docs/TRAIN.md "What crosses chips
# in an FSDP step, and when". Read from the optimized module's text, so it
# counts what the compiler scheduled, not what the annotations asked for.
_COLLECTIVE_OP = re.compile(
    r" = (?P<type>.*?) (?P<kind>all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?P<start>-start)?\(")
_ARRAY_TYPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_CALLED = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation|"
    r"branch_computations)=(%?[\w.\-]+|\{[^{}]*\})")


def _hlo_itemsize(dtype: str) -> int:
    """Bytes of one element of an HLO type name (``bf16``, ``s32``,
    ``f8e4m3fn``, ``pred``)."""
    bits = re.match(r"[a-z]+(\d+)", dtype)
    return max(1, int(bits.group(1)) // 8) if bits else 1


def collective_table(compiled) -> List[Dict[str, Any]]:
    """The collectives of a compiled program (``jit(...).lower(...)
    .compile()``, or its ``as_text()``), one row per distinct ``kind``,
    ``dtype``, ``shape`` and ``depth``: ``{"kind", "dtype", "shape",
    "bytes", "depth", "count"}``. ``shape`` and ``bytes`` are those of the
    result a device holds afterwards (the gathered array of an all-gather,
    the shard of a reduce-scatter); a combined collective gives a row an
    array. ``depth`` is how many ``while`` loops enclose the instruction:
    in a train step with ``accum_steps > 1`` the microbatch loop is depth
    1 and a scanned model's layer loop depth 2, so a weight that is
    gathered once a step reads depth 0. ``count`` is instructions in the
    text, not executions: a row at depth 2 runs layers x microbatches
    times. Rows come deepest first, then by ``bytes x count``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    found: List[Tuple[str, str, str, Tuple[int, ...]]] = []
    # computation -> [(computation that calls it, 1 if as a loop)]
    callers: Dict[str, List[Tuple[str, int]]] = {}
    entry = here = ""
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            here = head.group(1)
            if line.startswith("ENTRY"):
                entry = here
            continue
        for attr, names in _CALLED.findall(line):
            for name in names.strip("{}").split(","):
                callers.setdefault(name.strip().lstrip("%"), []).append(
                    (here, int(attr in ("body", "condition"))))
        op = _COLLECTIVE_OP.search(line)
        if op is None:
            continue
        arrays = [(dtype, tuple(int(d) for d in dims.split(",") if d))
                  for dtype, dims in _ARRAY_TYPE.findall(
                      re.sub(r"\{[^{}]*\}", "", op.group("type")))]
        kind = op.group("kind")
        if op.group("start") and kind != "all-reduce":
            # (operands..., results...), and a collective-permute's two
            # context words behind them: keep the results
            if kind == "collective-permute":
                arrays = arrays[:-2]
            arrays = arrays[len(arrays) // 2:]
        found.extend((here, kind) + a for a in arrays)

    depths: Dict[str, int] = {entry: 0}

    def depth(name: str, seen=()) -> int:
        if name not in depths:
            depths[name] = max(
                (depth(c, seen + (name,)) + loop
                 for c, loop in callers.get(name, ()) if c not in seen),
                default=0)
        return depths[name]

    rows: Dict[Tuple, Dict[str, Any]] = {}
    for comp, kind, dtype, shape in found:
        key = (kind, dtype, shape, depth(comp))
        row = rows.setdefault(key, {
            "kind": kind, "dtype": dtype, "shape": shape,
            "bytes": math.prod(shape) * _hlo_itemsize(dtype),
            "depth": key[3], "count": 0})
        row["count"] += 1
    return sorted(rows.values(), key=lambda r: (
        -r["depth"], -r["bytes"] * r["count"], r["kind"], r["shape"]))


def build_eval_step(loss_fn, mesh, rules=None):
    def eval_step(params, batch):
        with axis_rules(mesh, rules):
            return loss_fn(params, batch)

    return jax.jit(eval_step)


def shard_batch(batch: Dict[str, jax.Array], mesh: Mesh,
                rules: Optional[Rules] = None):
    """Batch-shard every leaf: (batch, length) for rank >= 2 leaves, batch
    only for rank-1 leaves (labels, weights — image batches mix ranks)."""
    sh = batch_sharding(mesh, rules)
    sh1 = NamedSharding(mesh, spec_for(("batch",), rules))

    def put(x):
        return jax.device_put(x, sh1 if jnp.ndim(x) <= 1 else sh)

    return jax.tree.map(put, batch)
