"""Core-plane observability overhead benchmark (ISSUE 11 + 15).

Four rows, all instrumented-vs-uninstrumented with the <2% acceptance
bar of the PR 9 trace bench:

* ``obs_rpc_overhead_pct`` — the RPC microbench hot path (inline ping
  round-trips through the reactor write path) with
  ``core_metrics_enabled`` on vs off. The write path's instruments are
  plain attribute increments under locks it already holds, plus two
  clock reads per reactor flush; this row proves that stays noise.
* ``obs_decode_step_overhead_pct`` — the steady decode step loop (the
  PR 9 trace-overhead scenario) with the core-plane instruments armed
  vs stripped, PR 9 observability at defaults both ways.
* ``obs_pipe_trace_overhead_pct`` (ISSUE 15) — the pipeline-parallel
  1F1B step loop traced vs untraced (``pipe_trace_spans``: driver root
  span + driver cell spans + stage fwd/bwd/apply spans, all per
  stage-RPC, never per element).
* ``obs_pipe_flightrec_overhead_pct`` (ISSUE 15) — the same step loop
  with the flight recorder on vs off in EVERY process (the toggle is
  broadcast to the stage actors; on = deque appends + the background
  flusher).

The first two rows merge into BENCH_SERVE.json, the pipeline rows into
BENCH_TUNE.json (where the pipeline bench rows live), each preserving
every other row (PR 6 idiom). Run via ``make bench-obs``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time


def rpc_overhead_row(quick: bool, platform: str = ""):
    from ray_tpu.core.config import config
    from ray_tpu.core.rpc import RpcClient, RpcServer

    calls = 2000 if quick else 6000
    repeats = 4 if quick else 7

    srv = RpcServer({"ping": lambda: "pong"}, name="bench-obs",
                    inline_methods={"ping"})
    cli = RpcClient(srv.addr)
    old = config.core_metrics_enabled
    try:
        for _ in range(500):  # warm the path
            cli.call("ping")

        def segment(enabled: bool) -> float:
            config.core_metrics_enabled = enabled
            t0 = time.perf_counter()
            for _ in range(calls):
                cli.call("ping")
            return (time.perf_counter() - t0) / calls

        # Interleave on/off segments over ONE connection: clock drift
        # and scheduler noise on a 1-core host dwarf the delta being
        # measured, so the comparison must be local in time.
        on, off = [], []
        for _ in range(repeats):
            off.append(segment(False))
            on.append(segment(True))
    finally:
        config.core_metrics_enabled = old
        cli.close()
        srv.stop()
    t_off = statistics.median(off)
    t_on = statistics.median(on)
    overhead = (t_on - t_off) / t_off * 100.0
    return [{
        "metric": "obs_rpc_overhead_pct",
        "value": round(overhead, 2), "unit": "%",
        "note": (f"inline RPC round-trip {t_on * 1e6:.1f}us instrumented "
                 f"vs {t_off * 1e6:.1f}us stripped (median of {repeats} x "
                 f"{calls}-call segments; write-path counters + dial "
                 f"counters + reactor flush timing armed); bar <2%; "
                 f"{platform}"),
    }]


def decode_overhead_row(params, cfg, quick: bool, platform: str = ""):
    from ray_tpu.core.config import config
    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    slots = 4
    steps = 100 if quick else 200
    repeats = 4 if quick else 6
    capacity = 4096
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).tolist()
               for _ in range(slots)]

    def measure(enabled: bool) -> float:
        old = config.core_metrics_enabled
        config.core_metrics_enabled = enabled
        try:
            # PR 9 observability at DEFAULTS both ways: this row
            # isolates the core-plane delta on top of the traced loop.
            eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                               prefix_pool_entries=0)
            reqs = [eng.submit(p, max_new_tokens=capacity - 64)
                    for p in prompts]
            for _ in range(20):
                eng.step()
            samples = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(steps):
                    eng.step()
                samples.append((time.perf_counter() - t0) / steps)
            for r in reqs:
                eng.cancel(r.request_id)
            eng.step()
            eng.shutdown()
            return statistics.median(samples)
        finally:
            config.core_metrics_enabled = old

    t_off = measure(False)
    t_on = measure(True)
    overhead = (t_on - t_off) / t_off * 100.0
    return [{
        "metric": "obs_decode_step_overhead_pct",
        "value": round(overhead, 2), "unit": "%",
        "note": (f"decode step loop {t_on * 1e6:.0f}us core-instrumented "
                 f"vs {t_off * 1e6:.0f}us stripped per step (median of "
                 f"{repeats} x {steps}-step segments, {slots} active "
                 f"slots, PR 9 tracing defaults both ways); bar <2%; "
                 f"{platform}"),
    }]


def _set_flag_everywhere(plane, name: str, value) -> None:
    """Flip a config flag in the driver AND every stage actor process
    (the recorder/span gates read process-local config)."""
    from ray_tpu.core.config import config

    setattr(config, name, value)
    plane._group.broadcast(_member_set_flag, name, value)


def _member_set_flag(member, name, value):
    from ray_tpu.core.config import config

    setattr(config, name, value)
    return True


def pipeline_overhead_rows(quick: bool, platform: str = ""):
    """Traced-vs-untraced and recorder-on-vs-off on the 1F1B step
    loop (ISSUE 15 acceptance: both <2%). Interleaved on/off segments
    on ONE warmed plane, same discipline as the other rows."""
    import numpy as np

    import ray_tpu
    from ray_tpu.core.config import config
    from ray_tpu.models import llama
    from ray_tpu.train.pipeline_plane import PipelinePlane, microbatches

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    os.environ["RAY_TPU_VIRTUAL_SLICE"] = "4x4/4"
    # A 1F1B step is ~200 ms with LOW-FREQUENCY drift bigger than the
    # effect being measured (segments drift 190-230 ms over a minute;
    # see BENCH_NOTES). The interleaving granularity is ONE SAMPLING
    # PERIOD (pipe_trace_sample_every steps) per side: any span of
    # sample_every consecutive steps contains exactly one traced step
    # whatever the phase, so the on-segments carry the sampled cost
    # deterministically (single-step alternation ALIASES: period-2
    # toggling never lands an on-step on the period-4 sampling grid
    # and measures pure noise), while tight pairing still cancels the
    # drift.
    pairs = 4 if quick else 14

    cfg = llama.LlamaConfig(vocab_size=128, dim=64, n_layers=4,
                            n_heads=4, n_kv_heads=2, mlp_dim=128,
                            max_seq_len=128)
    import jax

    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)

    def step_data():
        return microbatches(
            {"tokens": rng.integers(0, cfg.vocab_size,
                                    (8, 65)).astype(np.int32)}, 8)

    rows = []
    # TPU: 0 skips init()'s chip probe: this driver already holds the chip
    # (the decode row ran in-process) and the stages are CPU workers.
    ray_tpu.init(num_cpus=8, resources={"TPU": 0})
    try:
        plane = PipelinePlane(cfg, params, n_stages=2, n_microbatches=8,
                              lr=1e-3, window=2, name="obs-pipe",
                              snapshot_every=0).start()
        try:
            plane.train_step(step_data())  # warm the stage jits
            seg_steps = max(1, config.pipe_trace_sample_every)

            def segment() -> float:
                t0 = time.perf_counter()
                for _ in range(seg_steps):
                    plane.train_step(step_data())
                return (time.perf_counter() - t0) / seg_steps

            for flag, metric, note_what in (
                    ("pipe_trace_spans", "obs_pipe_trace_overhead_pct",
                     "driver root+cell spans + stage fwd/bwd/apply "
                     "spans"),
                    ("flightrec_enabled",
                     "obs_pipe_flightrec_overhead_pct",
                     "flight-recorder ring appends + background "
                     "flusher, toggled in every process")):
                on, off = [], []
                for _ in range(pairs):
                    _set_flag_everywhere(plane, flag, False)
                    off.append(segment())
                    _set_flag_everywhere(plane, flag, True)
                    on.append(segment())
                # MEAN, not median: the tracer head-samples (1 step in
                # pipe_trace_sample_every), so the steady-state cost
                # lives in the mean over whole sampling periods — a
                # median would report the untraced majority and hide
                # the sampled steps entirely.
                t_on = statistics.fmean(on)
                t_off = statistics.fmean(off)
                overhead = (t_on - t_off) / t_off * 100.0
                rows.append({
                    "metric": metric,
                    "value": round(overhead, 2), "unit": "%",
                    "note": (f"2-stage 8-microbatch 1F1B step "
                             f"{t_on * 1e3:.1f}ms on vs "
                             f"{t_off * 1e3:.1f}ms off ({note_what}; "
                             f"mean of {pairs} interleaved "
                             f"{seg_steps}-step on/off segments = one "
                             f"sampling period per side, default "
                             f"head-sampling config); bar <2%; "
                             f"{platform}"),
                })
            # Leave the defaults on for whoever runs next.
            _set_flag_everywhere(plane, "pipe_trace_spans",
                                 config.pipe_trace_spans)
        finally:
            plane.stop()
    finally:
        ray_tpu.shutdown()
    return rows


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--model", default=None)
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()

    import jax

    if args.quick or args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    from ray_tpu.models import llama

    preset = args.model or ("debug" if args.quick else "160m")
    cfg = llama.PRESETS[preset]
    params = llama.init_params(cfg, jax.random.key(0))
    platform = jax.devices()[0].platform
    plat_note = f"{preset} model, {platform} backend"

    rows = rpc_overhead_row(args.quick, plat_note)
    rows += decode_overhead_row(params, cfg, args.quick, plat_note)

    out_path = "BENCH_SERVE.json"
    doc = {"artifact": "BENCH_SERVE", "rows": []}
    if os.path.exists(out_path) and not args.quick:
        with open(out_path) as f:
            doc = json.load(f)
        emitted = {r["metric"] for r in rows}
        doc["rows"] = [r for r in doc.get("rows", [])
                       if r["metric"] not in emitted]
    if args.quick:
        out_path = "/tmp/bench_obs_quick.json"
    doc["rows"] = doc.get("rows", []) + rows
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(json.dumps(rows))

    # Pipeline step-loop rows live with the other pipeline bench rows
    # in BENCH_TUNE.json (merge-preserving, incl. the PBT artifact).
    pipe_rows = pipeline_overhead_rows(args.quick, plat_note)
    tune_path = "BENCH_TUNE.json"
    tune_doc = {}
    if os.path.exists(tune_path) and not args.quick:
        with open(tune_path) as f:
            tune_doc = json.load(f)
    emitted = {r["metric"] for r in pipe_rows}
    tune_doc["rows"] = [r for r in tune_doc.get("rows", [])
                        if r["metric"] not in emitted] + pipe_rows
    if args.quick:
        tune_path = "/tmp/bench_obs_pipe_quick.json"
    with open(tune_path, "w") as f:
        json.dump(tune_doc, f, indent=2)
    print(json.dumps(pipe_rows))


if __name__ == "__main__":
    main()
