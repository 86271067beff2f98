"""ViT model family tests: learning, sharded-vs-dense parity, trainer run."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import vit


def _synthetic_batch(cfg, n=64, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(n, cfg.image_size, cfg.image_size,
                              cfg.channels)).astype(np.float32)
    labels = rng.integers(0, cfg.num_classes, n).astype(np.int64)
    return {"images": jnp.asarray(images), "labels": jnp.asarray(labels)}


def test_vit_overfits_synthetic():
    cfg = vit.PRESETS["debug"]
    params = vit.init_params(cfg, jax.random.key(0))
    batch = _synthetic_batch(cfg)
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: vit.loss_fn(p, batch, cfg), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, aux

    first = None
    for i in range(60):
        params, opt_state, loss, aux = step(params, opt_state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))
    assert float(aux["accuracy"]) > 0.8


def test_vit_sharded_loss_matches_dense():
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    cfg = vit.PRESETS["debug"]
    mesh = MeshSpec(data=2, tensor=2, fsdp=-1).build()
    params = ts.init_sharded_params(
        lambda k: vit.init_params(cfg, k), vit.param_axes(), mesh,
        jax.random.key(0))
    batch_np = _synthetic_batch(cfg, n=16)
    opt = optax.adamw(1e-3)
    opt_state = ts.init_optimizer_state(opt, params)
    step_fn = ts.build_train_step(
        lambda p, b: vit.loss_fn(p, b, cfg)[0], opt, mesh)
    data = ts.shard_batch(dict(batch_np), mesh)
    _, _, metrics = step_fn(params, opt_state, data)
    sharded_loss = float(metrics["loss"])

    dense_params = vit.init_params(cfg, jax.random.key(0))
    dense_loss = float(vit.loss_fn(dense_params, batch_np, cfg)[0])
    np.testing.assert_allclose(sharded_loss, dense_loss, rtol=2e-3)


@pytest.mark.timeout_s(240)
def test_vit_through_jax_trainer(ray_start_regular):
    """North-star shape: ViT training through JaxTrainer with
    session.report metrics."""
    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, ScalingConfig

    def loop(config):
        import jax as j
        import optax as ox

        from ray_tpu.models import vit as v

        cfg = v.PRESETS["debug"]
        params = v.init_params(cfg, j.random.key(0))
        opt = ox.adamw(1e-3)
        opt_state = opt.init(params)

        @j.jit
        def step(params, opt_state, batch):
            (loss, aux), grads = j.value_and_grad(
                lambda p: v.loss_fn(p, batch, cfg), has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return ox.apply_updates(params, updates), opt_state, loss

        rng = np.random.default_rng(0)
        for it in range(3):
            batch = {
                "images": rng.normal(size=(8, cfg.image_size,
                                           cfg.image_size,
                                           cfg.channels)).astype(np.float32),
                "labels": rng.integers(0, cfg.num_classes, 8),
            }
            params, opt_state, loss = step(params, opt_state, batch)
            train.report({"loss": float(loss), "iter": it})

    result = JaxTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker={"CPU": 1})).fit()
    assert result.error is None, result.error
    assert "loss" in result.metrics


def test_pad_tokens_to_is_exact():
    """Tile-friendly token padding (pad_tokens_to) changes only the MXU
    tiling: logits match the unpadded model bit-for-tolerance (padded
    keys masked in attention, pool slices them off)."""
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import vit

    base = vit.ViTConfig(image_size=16, patch_size=4, dim=64, n_layers=2,
                         n_heads=2, mlp_dim=128, num_classes=10)
    padded = dataclasses.replace(base, pad_tokens_to=32)  # 16 -> 32 tokens
    params = vit.init_params(base, jax.random.key(0))
    images = jax.random.normal(jax.random.key(1), (3, 16, 16, 3))
    out_base = np.asarray(vit.forward(params, images, base))
    out_pad = np.asarray(vit.forward(params, images, padded))
    np.testing.assert_allclose(out_pad, out_base, rtol=2e-2, atol=2e-2)
    # Gradients agree too (the whole padded path is differentiable-exact).
    g1 = jax.grad(lambda p: vit.loss_fn(
        p, {"images": images, "labels": jax.numpy.zeros(3, jax.numpy.int32)},
        base)[0])(params)
    g2 = jax.grad(lambda p: vit.loss_fn(
        p, {"images": images, "labels": jax.numpy.zeros(3, jax.numpy.int32)},
        padded)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-2, atol=5e-2)
