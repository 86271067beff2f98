"""What crosses chips in an FSDP step with gradient accumulation, counted
in the compiled program (docs/TRAIN.md "What crosses chips in an FSDP
step, and when"): the bfloat16 compute copy is gathered once a step,
outside the microbatch loop; a gradient is pinned to its parameter's
shard; the head's gradient is reduce-scattered once a microbatch; and the
numbers are the single-device step's.

The counts here are the CPU partitioner's, on virtual devices; what the
chip's own compiler makes of the benchmark's step is in
``tests/test_tpu_compile.py``. The shapes keep every weight larger than a
microbatch's activations on a device, so the partitioner has no cheaper
way out than moving the weight."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.mesh import MeshSpec

CFG = dataclasses.replace(
    llama.PRESETS["debug"], dim=128, mlp_dim=512, n_heads=4, n_kv_heads=2,
    vocab_size=384, max_seq_len=16, fused_qkv=True, fused_mlp=True,
    embed_via_matmul=True, loss_chunk=8, embed_chunk=16)
SEQ = 16
MESHES = {
    "fsdp4": (MeshSpec(fsdp=-1), 4),
    "fsdp8": (MeshSpec(fsdp=-1), 8),
    "data2_fsdp2": (MeshSpec(data=2, fsdp=-1), 4),
    "data2_fsdp4": (MeshSpec(data=2, fsdp=-1), 8),
}


def _loss(cfg):
    return lambda p, b: llama.loss_fn(p, b, cfg)


def _state(mesh_name, cfg=CFG, optimizer=None, accum=4, items=None):
    spec, n = MESHES[mesh_name] if mesh_name else (MeshSpec(fsdp=1), 1)
    mesh = spec.build(jax.devices()[:n])
    params = ts.init_sharded_params(
        lambda k: llama.init_params(cfg, k), llama.param_axes(cfg), mesh,
        jax.random.key(0))
    opt = optimizer or optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    step = ts.build_train_step(_loss(cfg), opt, mesh, accum_steps=accum)
    batch = ts.shard_batch({"tokens": jax.random.randint(
        jax.random.key(1), (items or accum * n, SEQ + 1), 0,
        cfg.vocab_size)}, mesh)
    return step, params, opt_state, batch


def _dims(shape):
    """A shape without its unit axes and their order: a layer sliced out
    of a stack, or a transposed copy, is the same weight."""
    return tuple(sorted(d for d in shape if d != 1))


def _weights(params):
    """Dims of every matrix of the model, whole and as one layer's slice
    (norm vectors are too small to tell from an activation)."""
    out = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if leaf.ndim < 2 or "norm" in jax.tree_util.keystr(path):
            continue
        out.add(_dims(leaf.shape))
        if "layers" in jax.tree_util.keystr(path):
            out.add(_dims(leaf.shape[1:]))
    return out


@pytest.fixture(scope="module", params=sorted(MESHES))
def compiled(request):
    step, params, opt_state, batch = _state(request.param)
    program = step.lower(params, opt_state, batch).compile()
    return request.param, params, ts.collective_table(program)


def test_no_weight_is_gathered_inside_the_microbatch_loop(compiled):
    _, params, table = compiled
    weights = _weights(params)
    inside = [r for r in table if r["kind"] == "all-gather" and r["depth"]
              and _dims(r["shape"]) in weights]
    assert not inside, inside


def test_every_weight_is_gathered_whole_once_a_step(compiled):
    _, params, table = compiled
    once = {_dims(r["shape"]): r["count"] for r in table
            if r["kind"] == "all-gather" and r["depth"] == 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if leaf.ndim >= 2 and "norm" not in jax.tree_util.keystr(path):
            assert once.get(_dims(leaf.shape)) == 1, (path, once)


def test_head_gradient_is_reduce_scattered_once_a_microbatch(compiled):
    name, params, table = compiled
    fsdp = dict(zip(("data", "fsdp"), MESHES[name][0].sizes(
        MESHES[name][1])))["fsdp"]
    head = params["lm_head"].shape
    scatters = [r for r in table if r["kind"] == "reduce-scatter"]
    assert [(r["shape"], r["depth"], r["count"]) for r in scatters] == [
        ((head[0] // fsdp, head[1]), 1, 1)], scatters
    whole = [r for r in table if r["kind"] == "all-reduce"
             and r["shape"] == head]
    assert not whole, whole


def test_one_device_step_lowers_to_the_text_it_had():
    """``accum_steps=1`` on one device: no constraint is placed, so the
    module is the plain step's, the text ``build_train_step`` gave before
    it read any layout."""
    from ray_tpu.parallel.sharding import axis_rules

    step, params, opt_state, batch = _state(None, accum=1, items=2)
    opt = optax.adamw(1e-3)
    mesh = params["lm_head"].sharding.mesh

    def train_step(params, opt_state, batch):
        with axis_rules(mesh, None):
            loss, grads = jax.value_and_grad(_loss(CFG))(params, batch)
        updates, new_opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state, {
            "loss": loss, "grad_norm": optax.global_norm(grads)}

    plain = jax.jit(train_step, donate_argnums=(0, 1))
    assert step.lower(params, opt_state, batch).as_text() == \
        plain.lower(params, opt_state, batch).as_text()


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("mesh_name", ["fsdp4", "data2_fsdp4"])
def test_sharded_step_matches_the_single_device_step(mesh_name, accum):
    """Loss, gradient norm and every updated parameter against the same
    step on one device from the same seed: float32 model, plain SGD (an
    update is the gradient times 0.1), so what differs is the order of a
    float32 sum. With accumulation a microbatch's gradient is bfloat16
    (the compute copy's dtype, and the wire's), so a re-ordered sum may
    round a few elements the other way: one bfloat16 step of the leaf's
    largest gradient bounds those."""
    cfg = dataclasses.replace(CFG, dtype=jnp.float32)

    def run(name):
        step, params, opt_state, batch = _state(
            name, cfg=cfg, optimizer=optax.sgd(0.1), accum=accum, items=32)
        before = jax.tree.map(np.asarray, params)
        params, _, metrics = step(params, opt_state, batch)
        return jax.tree.map(np.asarray, params), metrics, before

    want, want_m, before = run(None)
    got, got_m, _ = run(mesh_name)
    np.testing.assert_allclose(float(got_m["loss"]), float(want_m["loss"]),
                               rtol=2e-6)
    np.testing.assert_allclose(float(got_m["grad_norm"]),
                               float(want_m["grad_norm"]), rtol=1e-5)
    for (path, a), b, p0 in zip(jax.tree_util.tree_leaves_with_path(want),
                                jax.tree.leaves(got),
                                jax.tree.leaves(before)):
        name = jax.tree_util.keystr(path)
        if accum == 1:
            np.testing.assert_allclose(b, a, rtol=0, atol=2e-6, err_msg=name)
            continue
        assert np.mean(np.abs(b - a) > 2e-6) < 1e-3, name
        np.testing.assert_allclose(
            b, a, rtol=0, atol=2e-6 + np.abs(a - p0).max() * 2.0 ** -8,
            err_msg=name)


def test_layout_follows_the_parameters_own_shardings(monkeypatch):
    """A gradient is pinned to its parameter's sharding; the compute copy
    loses the axes the batch is cut over and keeps the others; replicated
    parameters (ZeRO-1's) and a step that does not accumulate pin no copy;
    a copy that cannot fit the device stays a shard."""
    mesh = MeshSpec(data=2, fsdp=2, tensor=2).build()
    params = ts.init_sharded_params(
        lambda k: llama.init_params(CFG, k), llama.param_axes(CFG), mesh,
        jax.random.key(0))
    opt = optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    batch = ts.shard_batch({"tokens": jnp.zeros((16, SEQ + 1), jnp.int32)},
                           mesh)
    step = ts.build_train_step(_loss(CFG), opt, mesh, accum_steps=4)
    grads, copies = step._layout(params, opt_state, batch)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert list(grads) == [leaf.sharding for _, leaf in leaves]
    by_name = {jax.tree_util.keystr(path): copy
               for (path, _), copy in zip(leaves, copies)}
    assert by_name["['lm_head']"].spec == P(None, "tensor")
    assert by_name["['tok_embed']"].spec == P("tensor")
    assert by_name["['layers']['w_down']"].spec == P(None, "tensor")
    assert by_name["['final_norm']"].spec == P()

    plain = ts.build_train_step(_loss(CFG), opt, mesh)
    assert plain._layout(params, opt_state, batch) == (
        grads, (None,) * len(grads))

    replicated = jax.device_put(params, NamedSharding(mesh, P()))
    nothing = (None,) * len(grads)
    assert step._layout(replicated, opt_state, batch) == (nothing, nothing)

    monkeypatch.setattr(ts, "_bytes_limit", lambda device: 1 << 20)
    tight = ts.build_train_step(_loss(CFG), opt, mesh, accum_steps=4)
    assert tight._layout(params, opt_state, batch) == (grads, nothing)
    monkeypatch.setattr(ts, "_bytes_limit", lambda device: 1 << 40)
    roomy = ts.build_train_step(_loss(CFG), opt, mesh, accum_steps=4)
    assert roomy._layout(params, opt_state, batch) == (grads, copies)


HLO = """\
HloModule jit_train_step

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%layers (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %p = (s32[], bf16[8,4]{1,0:T(8,128)(2,1)}) parameter(0)
  %w = bf16[8,4]{1,0:T(8,128)(2,1)} get-tuple-element(%p), index=1
  %ag = bf16[8,16]{1,0:T(8,128)(2,1)S(1)} all-gather(%w), dimensions={1}
  %cps = (bf16[8,4]{1,0}, bf16[8,4]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%w), source_target_pairs={{0,1}}
  %cpd = bf16[8,4]{1,0} collective-permute-done(%cps)
  ROOT %t = (s32[], bf16[8,4]) tuple(%i, %cpd)
}

%micro (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %p = (s32[], bf16[8,4]) parameter(0)
  %inner = (s32[], bf16[8,4]) while(%p), condition=%cond, body=%layers
  %g = bf16[8,4] get-tuple-element(%inner), index=1
  %rs = bf16[2,4]{1,0} reduce-scatter(%g), dimensions={0}, to_apply=%add
  %ar = (f32[16]{0}, f32[]) all-reduce(%x, %y), to_apply=%add
  ROOT %t = (s32[], bf16[8,4]) tuple(%i, %g)
}

%cond (p: (s32[], bf16[8,4])) -> pred[] {
  %p = (s32[], bf16[8,4]) parameter(0)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (w: bf16[2,4]) -> bf16[8,4] {
  %w = bf16[2,4]{1,0} parameter(0)
  %ags = (bf16[2,4]{1,0}, bf16[8,4]{1,0}) all-gather-start(%w), dimensions={0}
  %agd = bf16[8,4]{1,0} all-gather-done(%ags)
  %loop = (s32[], bf16[8,4]) while(%init), condition=%cond, body=%micro
  ROOT %out = bf16[8,4] get-tuple-element(%loop), index=1
}
"""


def test_collective_table_reads_kind_shape_bytes_and_loop_depth():
    rows = {(r["kind"], r["dtype"], r["shape"], r["depth"]):
            (r["bytes"], r["count"]) for r in ts.collective_table(HLO)}
    assert rows == {
        ("all-gather", "bf16", (8, 16), 2): (256, 1),
        ("collective-permute", "bf16", (8, 4), 2): (64, 1),
        ("reduce-scatter", "bf16", (2, 4), 1): (16, 1),
        ("all-reduce", "f32", (16,), 1): (64, 1),
        ("all-reduce", "f32", (), 1): (4, 1),
        ("all-gather", "bf16", (8, 4), 0): (64, 1),
    }
    depths = [r["depth"] for r in ts.collective_table(HLO)]
    assert depths == sorted(depths, reverse=True)
