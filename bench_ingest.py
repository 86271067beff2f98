"""Ingest-overlap benchmark: does device-prefetch remove fetch wait from
the step budget? (VERDICT r4 Missing #5 — the proof row.)

Three configurations of the same jitted Llama train step:
  resident   — the batch lives on device; pure step time (floor)
  sync       — each step pulls the next batch from a Dataset and
               device_puts it INLINE (fetch sits inside the step budget,
               the round-4 state of affairs)
  prefetch   — ``iter_device_batches(prefetch=2)``: a background thread
               assembles + dispatches the next transfer while the step
               runs

Prints one JSON line; run on the chip: ``python bench_ingest.py``
(CPU smoke: ``JAX_PLATFORMS=cpu python bench_ingest.py --quick``).
"""

from __future__ import annotations

import argparse
import json
import time

from ray_tpu.util import compile_cache

compile_cache.configure()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    args = parser.parse_args()

    import jax

    if args.quick:  # jax already read JAX_PLATFORMS at import
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec

    if args.quick:
        cfg = llama.PRESETS["debug"]
        batch, seq, steps, blocks = 8, 64, 20, 8
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, dim=768, n_layers=12, n_heads=12,
            n_kv_heads=12, mlp_dim=2048, max_seq_len=2048,
            attention_impl="flash", fused_qkv=True, fused_mlp=True,
            loss_chunk=1024)
        batch, seq, steps, blocks = 16, 1024, 30, 10

    ray_tpu.init(num_cpus=4)
    try:
        mesh = MeshSpec().build()  # single chip: trivial (fsdp=1) mesh
        params = ts.init_sharded_params(
            lambda k: llama.init_params(cfg, k), llama.param_axes(cfg),
            mesh, jax.random.key(0))
        opt = optax.adamw(1e-3)
        opt_state = ts.init_optimizer_state(opt, params)
        step_fn = ts.build_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh)

        rng = np.random.default_rng(0)
        n_rows = batch * steps
        raw = rng.integers(0, 2 ** 16, (n_rows, seq + 1)).astype(np.uint16)
        vocab = cfg.vocab_size

        def preprocess(block):
            # Stand-in for real pipeline work (decode/tokenize/augment):
            # a hash-map of raw u16 codes into the vocab. Runs on the
            # HOST per batch — exactly the work prefetch must overlap.
            x = block["raw"].astype(np.int64)
            for _ in range(8):  # ~tens of ms at bench shapes
                x = (x * 1664525 + 1013904223) & 0xFFFFFFFF
            return {"tokens": (x % vocab).astype(np.int32)}

        ds = rdata.from_numpy({"raw": raw},
                              num_blocks=blocks).map_batches(preprocess)

        def run(batches, n):
            """Trainer-shaped loop: metrics are fetched EVERY step (the
            session.report pattern), so per-step fetch + host batch
            production sit on the critical path unless prefetch moves
            them under the previous step's device time."""
            nonlocal params, opt_state
            t0 = time.perf_counter()
            count = 0
            for b in batches:
                params, opt_state, m = step_fn(params, opt_state, b)
                _ = float(m["loss"])  # per-step host fetch
                count += 1
                if count >= n:
                    break
            return (time.perf_counter() - t0) / count

        resident = ts.shard_batch(
            {"tokens": jax.numpy.asarray(
                preprocess({"raw": raw[:batch]})["tokens"])}, mesh)
        # Warmup to the compile FIXED POINT: call two steps — the second
        # call recompiles once (the donated outputs' sharding signature
        # differs from the freshly-initialized params), and only then is
        # the program stable.
        run(iter([resident] * 2), 2)

        t_resident = run(iter([resident] * steps), steps)

        def sync_iter():
            for hb in ds.iter_batches(batch_size=batch, pad_to=batch):
                yield ts.shard_batch(hb, mesh)

        t_sync = run(sync_iter(), steps)
        t_pref = run(ds.iter_device_batches(batch_size=batch, mesh=mesh,
                                            prefetch=2), steps)

        fetch_gap_sync = t_sync - t_resident
        fetch_gap_pref = t_pref - t_resident
        recovered = (1.0 - fetch_gap_pref / fetch_gap_sync
                     if fetch_gap_sync > 1e-9 else 1.0)
        out = {
            "metric": "ingest_overlap_llama160m" + (
                "_quick" if args.quick else ""),
            "step_resident_s": round(t_resident, 4),
            "step_sync_ingest_s": round(t_sync, 4),
            "step_prefetch_ingest_s": round(t_pref, 4),
            "fetch_gap_sync_ms": round(fetch_gap_sync * 1e3, 1),
            "fetch_gap_prefetch_ms": round(fetch_gap_pref * 1e3, 1),
            "fetch_gap_recovered_pct": round(100 * recovered, 1),
            "batch": batch, "seq": seq, "steps": steps,
        }
        print(json.dumps(out))
        with open("BENCH_INGEST.json" if not args.quick
                  else "/tmp/bench_ingest_quick.json", "w") as f:
            json.dump(out, f, indent=1)
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
