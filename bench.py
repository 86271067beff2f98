"""Headline benchmark: Llama train-step MFU on the local TPU chip(s).

Run by the driver on real hardware at the end of every round. Prints ONE
JSON line:
    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

Methodology: K training steps run inside ONE jitted ``lax.scan`` with
donated (params, opt_state) carry, and the timing bracket ends with a host
fetch of the final loss, so the bracket closes on finished work. MFU counts
model FLOPs only (6N + attention) against the chip's NOMINAL peak
(``ray_tpu.tpu.peak_flops_per_chip`` — an unknown device kind is an error);
remat recompute is NOT counted as useful work. vs_baseline = MFU / 40% (the
BASELINE.md north-star: Llama-2-7B >= 40% MFU on v5e-256; on one chip we
bench the largest preset of the same architecture/kernel mix that fits).

Config ladder: best-known-first (fused projections + Pallas flash
attention + chunked CE, shapes chosen to fit HBM); a config that fails
steps down the ladder. Any other failed phase fails the run. (ROADMAP S1
replaces the ladder with fixed cells.)
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp


def candidate_configs(env_preset=None):
    """(name, config, total_batch, seq, accum_steps) ladder."""
    from ray_tpu.models import llama

    if env_preset:
        cfg = llama.PRESETS[env_preset]
        return [(env_preset, cfg, 8, min(2048, cfg.max_seq_len), 1)]
    d1152 = llama.LlamaConfig(
        vocab_size=32000, dim=1152, n_layers=24, n_heads=9, n_kv_heads=9,
        mlp_dim=4608, max_seq_len=2048, attention_impl="flash",
        loss_chunk=1024, fused_qkv=True, fused_mlp=True,
        embed_via_matmul=True, embed_chunk=1024)
    d1280 = dataclasses.replace(d1152, dim=1280, n_heads=10, n_kv_heads=10,
                                mlp_dim=5120)
    return [
        # 16 accumulation microbatches amortize the bandwidth-bound AdamW
        # pass further than 8 (probe: 46.4% vs 46.0%); step time doubles
        # but the scan keeps the program inside the compile envelope.
        ("bench711m_s2048_b3x16", d1280, 48, 2048, 16),
        ("bench711m_s2048_b3x8", d1280, 24, 2048, 8),
        ("bench583m_s2048_b3x8", d1152, 24, 2048, 8),
        ("bench583m_s2048_b6x4", d1152, 24, 2048, 4),
        ("bench583m_s2048_b24", d1152, 24, 2048, 1),
        ("bench583m_s1024_b48",
         dataclasses.replace(d1152, max_seq_len=1024, loss_chunk=512),
         48, 1024, 1),
        ("bench583m_s2048_b16",
         dataclasses.replace(d1152, loss_chunk=512), 16, 2048, 1),
        ("bench583m_xla_b8",
         dataclasses.replace(d1152, attention_impl="xla", fused_qkv=False,
                             fused_mlp=False, embed_via_matmul=False,
                             loss_chunk=512), 8, 2048, 1),
        ("bench160m_b8", dataclasses.replace(
            llama.PRESETS["160m"], loss_chunk=512), 8, 2048, 1),
    ]


def run_one(cfg, batch: int, seq: int, steps: int, accum: int = 1):
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.sharding import axis_rules
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = MeshSpec(fsdp=-1).build()
    opt = optax.adamw(3e-4, weight_decay=0.1)
    params = ts.init_sharded_params(
        lambda k: llama.init_params(cfg, k), llama.param_axes(cfg), mesh,
        jax.random.key(0))
    opt_state = ts.init_optimizer_state(opt, params)

    def body(carry, tokens):
        # One optimizer step; with accum > 1 the framework's accumulation
        # path (hoisted bf16 cast + fp32 grad scan) amortizes the
        # bandwidth-bound optimizer/cast over accum microbatches
        # (ray_tpu.parallel.train_step.build_train_step semantics).
        p, o = carry
        with axis_rules(mesh):
            if accum == 1:
                loss, grads = jax.value_and_grad(
                    lambda pp: llama.loss_fn(pp, {"tokens": tokens}, cfg))(p)
            else:
                pbf = jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16)
                    if x.dtype == jnp.float32 else x, p)

                def micro(g_acc, mtoks):
                    loss, g = jax.value_and_grad(
                        lambda pp: llama.loss_fn(
                            pp, {"tokens": mtoks}, cfg))(pbf)
                    return jax.tree.map(
                        lambda a, b: a + b.astype(a.dtype), g_acc, g), loss

                g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                  p)
                mb = tokens.reshape(accum, tokens.shape[0] // accum,
                                    tokens.shape[1])
                grads, losses = jax.lax.scan(micro, g0, mb)
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = losses.mean()
            updates, o2 = opt.update(grads, o, p)
            p2 = optax.apply_updates(p, updates)
        return (p2, o2), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def multi(params, opt_state, toks):
        (p, o), losses = jax.lax.scan(body, (params, opt_state), toks)
        return p, o, losses

    toks = jax.device_put(
        jax.random.randint(jax.random.key(1), (steps, batch, seq + 1), 0,
                           cfg.vocab_size),
        NamedSharding(mesh, P(None, ("data", "fsdp"), None)))
    params, opt_state, losses = multi(params, opt_state, toks)
    _ = float(losses[-1])  # drain warmup
    best_dt = None
    for _rep in range(3):  # best-of-3
        t0 = time.perf_counter()
        params, opt_state, losses = multi(params, opt_state, toks)
        loss = float(losses[-1])
        dt = (time.perf_counter() - t0) / steps
        best_dt = dt if best_dt is None else min(best_dt, dt)
    return best_dt, loss


def run_vit(steps: int = 4, batch: int = 256):
    """Second model family (VERDICT r3 #10): ViT-B/16 train-step MFU with
    the same timing discipline (jitted donated scan + host fetch,
    best-of-3). SINGLE-CHIP measurement (unsharded jit runs on the
    default device, so peak counts one chip — unlike the sharded llama
    path). Returns (mfu_pct, img_per_sec, step_time_s, batch)."""
    import optax

    from ray_tpu.models import vit
    from ray_tpu.tpu import peak_flops_per_chip

    cfg = vit.PRESETS["vit_b16"]
    params = vit.init_params(cfg, jax.random.key(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    peak = peak_flops_per_chip(
        getattr(jax.devices()[0], "device_kind", ""))
    fpi = vit.flops_per_image(cfg)

    def body(carry, batch_d):
        p, o = carry
        loss, grads = jax.value_and_grad(
            lambda pp: vit.loss_fn(pp, batch_d, cfg)[0])(p)
        updates, o2 = opt.update(grads, o, p)
        return (optax.apply_updates(p, updates), o2), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def multi(params, opt_state, images, labels):
        (p, o), losses = jax.lax.scan(
            body, (params, opt_state),
            {"images": images, "labels": labels})
        return p, o, losses

    imgs = jax.random.normal(
        jax.random.key(1), (steps, batch, cfg.image_size, cfg.image_size,
                            3)).astype(jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (steps, batch), 0,
                                cfg.num_classes)
    params, opt_state, losses = multi(params, opt_state, imgs, labels)
    _ = float(losses[-1])  # drain warmup
    best = None
    for _rep in range(3):
        t0 = time.perf_counter()
        params, opt_state, losses = multi(params, opt_state, imgs, labels)
        _ = float(losses[-1])
        dt = (time.perf_counter() - t0) / steps
        best = dt if best is None else min(best, dt)
    mfu = 100.0 * batch * fpi / best / peak
    return round(mfu, 2), round(batch / best), round(best, 4), batch


def main() -> None:
    from ray_tpu.models import llama
    from ray_tpu.tpu import peak_flops_per_chip

    devices = jax.devices()
    n = len(devices)
    kind = getattr(devices[0], "device_kind", "unknown")
    peak = peak_flops_per_chip(kind) * n
    steps = int(os.environ.get("RAY_TPU_BENCH_STEPS", "8"))
    env_preset = os.environ.get("RAY_TPU_BENCH_PRESET")
    env_batch = int(os.environ.get("RAY_TPU_BENCH_BATCH", "0"))

    last_err = None
    for name, cfg, batch, seq, accum in candidate_configs(env_preset):
        batch = env_batch or batch
        try:
            dt, loss = run_one(cfg, batch, seq, steps, accum)
            last_err = None
            break
        except Exception as e:  # noqa: BLE001 — OOM etc: step down the ladder
            last_err = e
    if last_err is not None:
        raise last_err

    tokens_per_sec = batch * seq / dt
    flops_per_tok = llama.flops_per_token(cfg, seq)
    mfu = 100.0 * tokens_per_sec * flops_per_tok / peak

    # Second model family row (corroborates whether the MFU ceiling is
    # shape-dependent). A failure here fails the run.
    vit_row = {}
    if os.environ.get("RAY_TPU_BENCH_VIT", "1") != "0":
        vmfu, img_s, vdt, vbatch = run_vit()
        vit_row = {"vit_b16_mfu": vmfu, "vit_b16_img_per_sec": img_s,
                   "vit_b16_step_time_s": vdt, "vit_b16_batch": vbatch}

    print(json.dumps({
        "metric": f"llama_{name}_train_mfu_{n}x_{kind.replace(' ', '_')}",
        "value": round(mfu, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / 40.0, 3),
        "tokens_per_sec": round(tokens_per_sec),
        "tokens_per_sec_per_chip": round(tokens_per_sec / n),
        "step_time_s": round(dt, 4),
        "batch": batch,
        "seq": seq,
        "params_m": round(cfg.num_params() / 1e6),
        "loss": loss,
        "timing": "scan+fetch (end-to-end)",
        **vit_row,
    }))


if __name__ == "__main__":
    main()
