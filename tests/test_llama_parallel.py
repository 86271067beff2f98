"""Model + parallelism tests on the virtual 8-device CPU mesh (SURVEY §4
takeaway (a) applied to SPMD: multi-chip behavior tested without chips)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.sharding import axis_rules, tree_shardings
from ray_tpu.ops.attention import attention


CFG = llama.PRESETS["debug"]


def _batch(key, cfg, batch=4, seq=32):
    return {"tokens": jax.random.randint(key, (batch, seq + 1), 0,
                                         cfg.vocab_size)}


def test_device_count():
    assert jax.device_count() == 8, "conftest must force 8 virtual devices"


def test_forward_shapes():
    params = llama.init_params(CFG, jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_chunked_attention_matches_xla():
    key = jax.random.key(1)
    q = jax.random.normal(key, (2, 64, 4, 16))
    k = jax.random.normal(jax.random.key(2), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.key(3), (2, 64, 2, 16))
    out_xla = attention(q, k, v, causal=True, impl="xla")
    out_chunk = attention(q, k, v, causal=True, impl="chunked", chunk_size=16)
    np.testing.assert_allclose(out_xla, out_chunk, atol=2e-5, rtol=2e-5)


def test_fsdp_training_step_runs_and_learns():
    mesh = MeshSpec(fsdp=8).build()
    params = ts.init_sharded_params(
        lambda k: llama.init_params(CFG, k), llama.param_axes(), mesh,
        jax.random.key(0))
    opt = optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    # adam's moments are sharded like the params they mirror, not piled on
    # device 0 (zeros_like gives XLA nothing to propagate a sharding from)
    for moments in (opt_state[0].mu, opt_state[0].nu):
        assert all(m.sharding == p.sharding for m, p in zip(
            jax.tree.leaves(moments), jax.tree.leaves(params)))
    step = ts.build_train_step(
        lambda p, b: llama.loss_fn(p, b, CFG), opt, mesh)
    batch = ts.shard_batch(_batch(jax.random.key(1), CFG, batch=8), mesh)
    losses = []
    for i in range(5):
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], f"loss did not drop: {losses}"


def test_sharded_loss_matches_single_device():
    """DP+TP sharded loss == unsharded loss (GSPMD correctness)."""
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(cfg, jax.random.key(0))
    batch = _batch(jax.random.key(1), cfg, batch=4, seq=16)
    loss_single = float(llama.loss_fn(params, batch, cfg))

    mesh = MeshSpec(data=2, fsdp=2, tensor=2).build()
    shardings = tree_shardings(mesh, llama.param_axes())
    sharded_params = jax.tree.map(jax.device_put, params, shardings)
    sharded_batch = ts.shard_batch(batch, mesh)
    loss_fn = ts.build_eval_step(lambda p, b: llama.loss_fn(p, b, cfg), mesh)
    loss_sharded = float(loss_fn(sharded_params, sharded_batch))
    # Relative bound: f32 reduction order differs between the GSPMD
    # partition and the single-device program; on the 8-device virtual
    # CPU mesh the drift is ~2e-4 relative on a ~6.0 loss, which the old
    # 1e-3 ABSOLUTE bound flagged spuriously.
    assert abs(loss_single - loss_sharded) < 1e-3 * max(1.0, abs(loss_single)), (
        f"{loss_single} vs {loss_sharded}")


def test_ring_attention_matches_dense():
    """Ring attention over the seq axis == single-device attention."""
    from ray_tpu.parallel.ring_attention import ring_attention

    mesh = MeshSpec(data=1, fsdp=1, seq=8).build()
    key = jax.random.key(0)
    b, s, h, d = 2, 128, 4, 16
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    dense = attention(q, k, v, causal=True, impl="xla")

    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, head_axis=None))(q, k, v)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_flow():
    from ray_tpu.parallel.ring_attention import ring_attention

    mesh = MeshSpec(seq=8, fsdp=1).build()
    q = jnp.ones((1, 64, 2, 8))
    k = jnp.ones((1, 64, 2, 8)) * 0.1
    v = jnp.ones((1, 64, 2, 8)) * 0.2

    def f(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, head_axis=None))

    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_sequence_parallel_model_loss_matches():
    """Full model with attention_impl='ring' on a seq-sharded mesh matches
    the dense single-device loss."""
    import dataclasses

    cfg = dataclasses.replace(llama.PRESETS["debug"], attention_impl="ring",
                              remat=False)
    dense_cfg = dataclasses.replace(cfg, attention_impl="xla")
    params = llama.init_params(cfg, jax.random.key(0))
    toks = _batch(jax.random.key(1), cfg, batch=2, seq=64)["tokens"]
    # Pre-split: the seq axis shards inputs/targets, so their length (not
    # length+1) must divide the seq mesh axis.
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    loss_dense = float(llama.loss_fn(params, batch, dense_cfg))

    mesh = MeshSpec(data=1, fsdp=1, seq=4, tensor=2).build()
    shardings = tree_shardings(mesh, llama.param_axes())
    sharded_params = jax.tree.map(jax.device_put, params, shardings)
    sharded_batch = ts.shard_batch(batch, mesh)
    loss_fn = ts.build_eval_step(lambda p, b: llama.loss_fn(p, b, cfg), mesh)
    loss_ring = float(loss_fn(sharded_params, sharded_batch))
    # Relative bound (see test_sharded_loss_matches_single_device).
    assert abs(loss_dense - loss_ring) < 1e-3 * max(1.0, abs(loss_dense)), (
        f"{loss_dense} vs {loss_ring}")


def test_mesh_spec_inference():
    spec = MeshSpec(data=2, fsdp=-1)
    assert spec.sizes(8) == (2, 4, 1, 1, 1)
    with pytest.raises(ValueError):
        MeshSpec(data=3).sizes(8)


def test_embed_via_matmul_matches_gather():
    import dataclasses

    import numpy as np

    cfg = llama.PRESETS["debug"]
    cfg2 = dataclasses.replace(cfg, embed_via_matmul=True)
    params = llama.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    l1 = float(llama.loss_fn(params, {"tokens": toks}, cfg))
    l2 = float(llama.loss_fn(params, {"tokens": toks}, cfg2))
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    g1 = jax.grad(lambda p: llama.loss_fn(p, {"tokens": toks}, cfg))(params)
    g2 = jax.grad(lambda p: llama.loss_fn(p, {"tokens": toks}, cfg2))(params)
    # bf16 matmul accumulation vs gather: one-ulp-level differences are
    # expected on a handful of elements.
    np.testing.assert_allclose(np.asarray(g1["tok_embed"]),
                               np.asarray(g2["tok_embed"]),
                               rtol=5e-2, atol=5e-4)


def test_train_step_gradient_accumulation():
    import dataclasses

    import numpy as np
    import optax

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    cfg = llama.PRESETS["debug"]
    mesh = MeshSpec(data=2, fsdp=-1).build()
    params = ts.init_sharded_params(
        lambda k: llama.init_params(cfg, k), llama.param_axes(cfg), mesh,
        jax.random.key(0))
    opt = optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    step = ts.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg), opt,
                               mesh, accum_steps=4)
    batch = ts.shard_batch(
        {"tokens": jax.random.randint(jax.random.key(1), (8, 65), 0,
                                      cfg.vocab_size)}, mesh)
    losses = []
    for _ in range(4):
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses  # accumulated grads still learn


def test_multislice_dcn_mesh_loss_matches():
    """MeshSpec(dcn_data=2): multi-slice layout (data replicas across
    slices over DCN, FSDP/TP inside each slice). On the virtual CPU mesh
    the slice split is emulated; loss must match the single-device value."""
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    spec = MeshSpec(dcn_data=2, tensor=2, fsdp=-1)
    assert spec.sizes(8) == (2, 2, 2, 1, 1)  # dcn folded into data axis
    mesh = spec.build()
    assert mesh.shape["data"] == 2 and mesh.shape["fsdp"] == 2

    cfg = llama.PRESETS["debug"]
    params = ts.init_sharded_params(
        lambda k: llama.init_params(cfg, k), llama.param_axes(), mesh,
        jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 33), 0, cfg.vocab_size)
    batch = ts.shard_batch({"tokens": toks}, mesh)

    import optax

    opt = optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    step_fn = ts.build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh)
    _, _, metrics = step_fn(params, opt_state, batch)
    sharded_loss = float(metrics["loss"])

    dense_params = llama.init_params(cfg, jax.random.key(0))
    dense_loss = float(llama.loss_fn(dense_params, {"tokens": toks}, cfg))
    # rtol matches the other loss-parity tests: reduction-order drift
    # on the virtual CPU mesh is ~1.5e-3 relative for this layout.
    np.testing.assert_allclose(sharded_loss, dense_loss, rtol=2e-3)


def test_flash_attention_under_a_sharded_mesh_matches_xla():
    """attention_impl="flash" inside a GSPMD step: the Pallas call runs
    under shard_map on each device's (batch, heads) shard (on the chip
    GSPMD refuses to partition a Mosaic kernel), GQA heads cut the same
    way on both head axes — the sharded loss matches the unsharded XLA
    path and the backward traces through the shard_map."""
    import dataclasses

    cfg = dataclasses.replace(CFG, dtype=jnp.float32)  # 4 heads / 2 kv
    flash = dataclasses.replace(cfg, attention_impl="flash")
    params = llama.init_params(cfg, jax.random.key(0))
    batch = _batch(jax.random.key(1), cfg, batch=4, seq=16)
    want = float(llama.loss_fn(params, batch, cfg))

    mesh = MeshSpec(fsdp=4, tensor=2).build()
    sharded = jax.tree.map(jax.device_put, params,
                           tree_shardings(mesh, llama.param_axes()))
    loss_fn = ts.build_eval_step(lambda p, b: llama.loss_fn(p, b, flash),
                                 mesh)
    got = float(loss_fn(sharded, ts.shard_batch(batch, mesh)))
    assert abs(got - want) < 1e-4 * max(1.0, abs(want)), (got, want)
    with axis_rules(mesh):
        grads = jax.eval_shape(
            jax.grad(lambda p: llama.loss_fn(p, batch, flash)), params)
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    # a seq-sharded mesh must use ring attention, not per-shard flash
    with pytest.raises(ValueError, match="ring"):
        with axis_rules(MeshSpec(fsdp=4, seq=2).build()):
            jax.eval_shape(lambda p: llama.loss_fn(p, batch, flash), params)
