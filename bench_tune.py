"""North-star Tune benchmark: a PBT sweep over ``Tuner(JaxTrainer(...))``
training ViT-B/16 on the attached TPU chip (BASELINE.md: "PBT sweep over
JaxTrainer ViT-B/16 on a pod slice" — here a 1-chip slice, trials
time-multiplexed through per-trial TPU placement groups).

What it proves (VERDICT r4 Missing #1): the reference's Train-runs-under-
Tune layering (``train/base_trainer.py:819`` + gang placement via
``tune/execution/placement_groups.py``) exists here — every trial is a
gang-scheduled WorkerGroup holding the chip through its own PG, PBT clones
donor state through orbax checkpoints and perturbs the lr, and the sweep's
per-trial overhead vs a solo ``JaxTrainer.fit`` is measured.

Run on the real chip: ``python bench_tune.py`` -> BENCH_TUNE.json
Smoke on CPU:         ``python bench_tune.py --quick``
"""

from __future__ import annotations

import argparse
import json
import shutil
import time

from ray_tpu.util import compile_cache

compile_cache.configure()


def vit_train_loop(config):
    """Per-trial loop: K jitted train steps per tune iteration (one lax.scan
    per iteration, donated state, host fetch ends the timing), loss + MFU
    reported every iteration, full (params, opt_state) orbax checkpoint
    every second iteration so PBT always has a donor to clone."""
    import functools
    import time as _time

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models import vit
    from ray_tpu.tpu import peak_flops_per_chip

    if config.get("tiny"):
        cfg = vit.PRESETS["debug"]
    else:
        cfg = vit.PRESETS["vit_b16"]
    steps = int(config.get("steps_per_iter", 20))
    batch = int(config.get("batch", 256))
    iters = int(config.get("iters", 6))
    lr = float(config["lr"])

    opt = optax.adamw(lr, weight_decay=0.1)
    params = vit.init_params(cfg, jax.random.key(0))
    opt_state = opt.init(params)
    start_iter = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:  # PBT exploit or resume: clone donor state
        (params, opt_state), meta = train.restore_pytree(
            ckpt, (params, opt_state))
        start_iter = int(meta.get("step", 0))

    # MFU is a chip metric: off the TPU (--quick) it is not computed.
    dev = jax.devices()[0]
    peak = (peak_flops_per_chip(dev.device_kind)
            if dev.platform == "tpu" else None)
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

    key = jax.random.key(1234)
    for it in range(start_iter, iters):
        key, k1, k2 = jax.random.split(key, 3)
        imgs = jax.random.normal(
            k1, (steps, batch, cfg.image_size, cfg.image_size, 3),
            jnp.float32)
        labels = jax.random.randint(k2, (steps, batch), 0,
                                    cfg.num_classes)
        t0 = _time.perf_counter()
        params, opt_state, losses = multi(params, opt_state, imgs, labels)
        loss = float(losses[-1])  # host fetch ends the timing
        dt = (_time.perf_counter() - t0) / steps
        metrics = {
            "loss": round(loss, 4),
            "mfu": (round(100.0 * batch * fpi / dt / peak, 2)
                    if peak else None),
            "step_time_s": round(dt, 4),
            "lr": lr,
            "iter": it + 1,
            # First iteration of a (re)launched trial pays the compile
            # (amortized across trials by the persistent compile cache).
            "compiled_this_iter": it == start_iter,
        }
        if (it + 1) % 2 == 0 or (it + 1) == iters:
            d = train.temp_checkpoint_dir()
            train.save_pytree(d, (params, opt_state), step=it + 1)
            train.report(metrics,
                         checkpoint=train.Checkpoint.from_directory(d))
            shutil.rmtree(d, ignore_errors=True)  # persisted copy remains
        else:
            train.report(metrics)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="tiny ViT on CPU devices: smoke the machinery")
    args = parser.parse_args()

    import ray_tpu
    from ray_tpu import tune
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.tune import PopulationBasedTraining, TuneConfig, Tuner

    class LoggingPBT(PopulationBasedTraining):
        """PBT that records every exploit event for the artifact."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.exploit_events = []

        def exploit_target(self, trial):
            donor = super().exploit_target(trial)
            if donor is not None:
                self.exploit_events.append({
                    "trial": trial.id,
                    "trial_lr": trial.config.get("lr"),
                    "donor": donor.id,
                    "donor_lr": donor.config.get("lr"),
                    "at_training_iteration": trial.iteration,
                })
            return donor

    quick = args.quick
    storage = "/tmp/ray_tpu_bench_tune"
    shutil.rmtree(storage, ignore_errors=True)

    base_cfg = {
        "tiny": quick,
        "steps_per_iter": 4 if quick else 20,
        "batch": 32 if quick else 256,
        "iters": 4 if quick else 6,
    }
    # Population: three sane lrs and one divergent one — the divergent
    # trial is the designed bottom-quantile member that must exploit.
    lrs = [1e-4, 3e-4, 1e-3, 3e-2]

    ray_tpu.init(num_cpus=4)
    try:
        use_tpu = not quick
        sc = ScalingConfig(
            num_workers=1,
            resources_per_worker={"CPU": 1.0},
            use_tpu=use_tpu,
            tpu_chips_per_worker=1 if use_tpu else 0,
        )
        trainer = JaxTrainer(
            vit_train_loop,
            train_loop_config=dict(base_cfg, lr=3e-4),
            scaling_config=sc,
            run_config=RunConfig(storage_path=storage),
        )

        # ---- solo fit baseline (sweep-overhead denominator)
        t0 = time.perf_counter()
        solo = trainer.fit()
        t_solo = time.perf_counter() - t0
        assert solo.error is None, solo.error
        solo_mfu = max((m["metrics"]["mfu"] for m in solo.metrics_history
                        if not m["metrics"]["compiled_this_iter"]
                        and m["metrics"]["mfu"] is not None), default=None)

        # ---- the PBT sweep
        scheduler = LoggingPBT(
            metric="loss", mode="min", perturbation_interval=2,
            hyperparam_mutations={"lr": [1e-4, 3e-4, 1e-3]}, seed=0)
        tuner = Tuner(
            trainer,
            param_space={"lr": tune.grid_search(lrs)},
            tune_config=TuneConfig(
                metric="loss", mode="min", scheduler=scheduler,
                # One chip: trials time-multiplex through their PGs.
                max_concurrent_trials=1),
            storage_path=storage,
            name="pbt_vit",
        )
        t0 = time.perf_counter()
        grid = tuner.fit()
        t_sweep = time.perf_counter() - t0

        trials = []
        losses_final = []
        for r in grid:
            hist = [m for m in r.metrics_history]
            best_loss = min((m["loss"] for m in hist), default=None)
            mfus = [m["mfu"] for m in hist
                    if not m.get("compiled_this_iter")
                    and m["mfu"] is not None]
            trials.append({
                "trial_id": r.trial_id,
                "final_config": r.config,
                "error": r.error,
                "iterations": len(hist),
                "final_loss": hist[-1]["loss"] if hist else None,
                "best_loss": best_loss,
                "mean_mfu": round(sum(mfus) / len(mfus), 2) if mfus
                else None,
                "loss_trajectory": [m["loss"] for m in hist],
            })
            if hist:
                losses_final.append(hist[-1]["loss"])
        losses_final.sort()
        n_trials_effective = len(trials) + len(scheduler.exploit_events)
        artifact = {
            "benchmark": "pbt_sweep_jaxtrainer_vit_b16"
            + ("_quick_cpu" if quick else ""),
            "population": len(lrs),
            "lr_grid": lrs,
            "perturbation_interval": 2,
            "iters_per_trial": base_cfg["iters"],
            "steps_per_iter": base_cfg["steps_per_iter"],
            "batch": base_cfg["batch"],
            "trials": trials,
            "exploit_events": scheduler.exploit_events,
            "best_final_loss": losses_final[0] if losses_final else None,
            "median_final_loss": losses_final[len(losses_final) // 2]
            if losses_final else None,
            "solo_fit_wall_s": round(t_solo, 1),
            "solo_fit_best_mfu": solo_mfu,
            "sweep_wall_s": round(t_sweep, 1),
            "sweep_overhead_vs_solo": round(
                t_sweep / (n_trials_effective * t_solo), 3)
            if t_solo > 0 else None,
        }
        out = "BENCH_TUNE_quick.json" if quick else "BENCH_TUNE.json"
        with open(out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(json.dumps({
            "metric": "pbt_vit_b16_sweep",
            "trials": len(trials),
            "exploits": len(scheduler.exploit_events),
            "best_final_loss": artifact["best_final_loss"],
            "median_final_loss": artifact["median_final_loss"],
            "sweep_overhead_vs_solo": artifact["sweep_overhead_vs_solo"],
        }))
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)


if __name__ == "__main__":
    main()
