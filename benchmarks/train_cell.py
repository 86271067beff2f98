"""A train cell: ``JaxTrainer`` on one worker that holds the cell's chips,
running ``train_loop`` below. The loop is the benchmark's; what it calls
(``MeshSpec``, ``init_sharded_params``, ``build_train_step``, the model's
``loss_fn``) is the program's."""

from __future__ import annotations

import math
import statistics
import time
from typing import Dict, List

from benchmarks import families, stats

WARM_STEPS = 2
REFERENCE_ITEMS = 4       # sequences / images the reference check runs on
TRACE_AT = 0.35           # the trace starts this far into the window
# System loss (bf16 matmuls, flash kernel) against the float32 reference on
# the same items and weights. Measured on the v5e (PR 23): |d| 0.0105-0.0145
# on the LM at losses of 3.6-7.8, 0.00005-0.0036 on the ViT: bf16 rounding
# through the layers, 0.1-0.3% of the loss. The bound is about three times
# the largest seen; dropping a layer or a wrong mask moves the loss by
# tenths.
LOSS_TOLERANCE = 0.05


def train_loop(config: Dict) -> None:
    """Runs inside the TrainWorker, the process that holds the chips."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.util.compile_cache import compile_watch

    watch = compile_watch()
    devices = jax.devices()
    dev0 = devices[0]
    job, seconds, traced = config["job"], config["seconds"], config["trace"]
    fam = families.load(config["family"]).Train(config["model"], job,
                                         config["flags"])
    seed = config["seed"] % (2 ** 31 - 1)
    mesh = MeshSpec(**job["mesh"]).build()
    params = ts.init_sharded_params(fam.init, fam.axes(), mesh,
                                    jax.random.key(seed))
    opt = optax.adamw(job["learning_rate"], weight_decay=job["weight_decay"])
    opt_state = ts.init_optimizer_state(opt, params)
    step_fn = ts.build_train_step(fam.loss, opt, mesh,
                                  accum_steps=int(job.get("accum", 1)))
    batch = ts.shard_batch(
        jax.jit(lambda k: fam.make_batch(k, fam.items))(
            jax.random.key(seed + 1)), mesh)

    # One ahead-of-time compile serves the steps and says how much scratch
    # memory the step takes: the runtime's ``peak_bytes_in_use`` counts the
    # live arrays (weights, optimizer state, batch) but not a running
    # program's temporaries (my chip runs, PR 23: 1.13 GB read while the
    # step's saved activations alone are larger), so the peak reported is
    # their sum.
    compiled = step_fn.lower(params, opt_state, batch).compile()
    analysis = compiled.memory_analysis()
    temp_bytes = int(getattr(analysis, "temp_size_in_bytes", 0) or 0)

    def one_step(params, opt_state):
        a = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:dispatch_step"):
            params, opt_state, metrics = compiled(params, opt_state, batch)
        with jax.profiler.TraceAnnotation("bench:wait_for_loss"):
            loss = float(metrics["loss"])   # host fetch: the step is done
        return params, opt_state, (a, time.monotonic(), loss)

    for _ in range(WARM_STEPS):
        params, opt_state, _ = one_step(params, opt_state)

    c0 = watch.snapshot()
    t_open = time.monotonic()
    open_wall = time.time()
    t_close = t_open + seconds
    steps: List = []
    trace_state = "off" if traced else "done"
    trace_t0 = 0.0
    while True:
        now = time.monotonic()
        if trace_state == "off" and now >= t_open + TRACE_AT * seconds:
            jax.profiler.start_trace(config["trace_dir"])
            trace_state, trace_t0 = "on", time.monotonic()
            first_traced = len(steps)
        elif trace_state == "on" and len(steps) >= first_traced + 2 and \
                now >= trace_t0 + config["trace_seconds"]:
            jax.profiler.stop_trace()
            trace_state = "done"
        params, opt_state, rec = one_step(params, opt_state)
        steps.append(rec)
        if rec[1] >= t_close:
            break
    if trace_state == "on":
        jax.profiler.stop_trace()
    c1 = watch.snapshot()
    mem = [d.memory_stats() or {} for d in devices]
    live_peak = max((m.get("peak_bytes_in_use") or 0) for m in mem)
    peak = live_peak + temp_bytes if live_peak else 0

    # Correctness, outside the window: the program's loss against the plain
    # reference on the same few items, at the weights as they are now.
    del opt_state
    sample = jax.tree.map(lambda x: x[:REFERENCE_ITEMS], batch)
    sample = ts.shard_batch(sample, mesh) if \
        REFERENCE_ITEMS % len(devices) == 0 else sample
    from ray_tpu.parallel.sharding import axis_rules

    def sys_loss(p, b):
        with axis_rules(mesh, None):
            return fam.loss(jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, p), b)

    loss_sys = float(jax.jit(sys_loss)(params, sample))
    loss_ref = float(jax.jit(fam.reference_loss)(params, sample))
    train.report({
        "final": True, "steps": steps, "t_open": t_open,
        "t_close": t_close, "open_wall": open_wall,
        "platform": dev0.platform, "device_kind": dev0.device_kind,
        "device_count": len(devices), "memory_peak_bytes": peak,
        "live_peak_bytes": live_peak, "step_temp_bytes": temp_bytes,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "compiles": c1, "loss_system": loss_sys, "loss_reference": loss_ref,
        "items": fam.items, "tokens_per_item": fam.tokens_per_item,
        "flops_per_token": fam.flops_per_token(),
        "mesh": dict(mesh.shape)})


def run(cell: Dict, args, t_proc_wall: float, work_dir: str) -> Dict:
    """Driver side: start the trainer, return the run's artefacts."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    cfg, job = cell["config"], cell["traffic"]
    chips = cell["chips"]
    loop_config = {
        "family": cfg["family"], "model": cfg["model"],
        "flags": cfg.get("train_flags", {}), "job": job,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "trace_dir": f"{work_dir}/trace", "trace_seconds": 4.0}
    result = JaxTrainer(
        train_loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=chips)).fit()
    if result.error:
        raise RuntimeError(f"train loop failed: {result.error}")
    final = result.metrics
    if not final or not final.get("final"):
        raise RuntimeError(f"train loop reported no final record: {final}")
    steps = [tuple(s) for s in final["steps"]]
    losses = [s[2] for s in steps]
    inside = stats.whole_steps([(a, b) for a, b, _ in steps],
                               final["t_open"], final["t_close"])
    rate = stats.whole_steps_rate(
        [(a, b) for a, b, _ in steps], final["t_open"], final["t_close"],
        final["items"] * final["tokens_per_item"], final["device_count"])
    delta = abs(final["loss_system"] - final["loss_reference"])
    bad = [x for x in losses if not math.isfinite(x)]
    correct = (not bad and losses[-1] < losses[0]
               and math.isfinite(delta) and delta <= LOSS_TOLERANCE)
    step_ms = [(b - a) * 1e3 for a, b in inside]
    print(f"[bench] {len(inside)} whole steps, median "
          f"{statistics.median(step_ms):.3f} ms; loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}; system {final['loss_system']:.5f} vs reference "
          f"{final['loss_reference']:.5f} (|d| {delta:.5f} <= "
          f"{LOSS_TOLERANCE}); mesh {final['mesh']}; memory: live peak "
          f"{final['live_peak_bytes'] / 1e9:.3f} GB + step temporaries "
          f"{final['step_temp_bytes'] / 1e9:.3f} GB; worker compiles "
          f"{final['compiles']}", flush=True)
    return {
        "kind": "train", "final": final, "correct": correct,
        "attempted": len(steps), "failed": len(bad),
        "end_to_end": {
            "train_tokens_per_s_per_chip": rate,
            "setup_s": final["open_wall"] - t_proc_wall},
        "step_ms": step_ms,
        "compiles_in_window": final["compiles_in_window"],
        "trace_dir": loop_config["trace_dir"] if args.trace else None,
        "device": {"platform": final["platform"],
                   "kind": final["device_kind"],
                   "count": final["device_count"],
                   "memory_peak_bytes": final["memory_peak_bytes"]}}
