#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

A new process each time. It finds the cell in ``BENCHMARK.json``, its
configuration under ``configs/`` and its traffic under ``traffic/`` by name,
starts the runtime, hands the chip to a worker (this process never
initialises a JAX backend), measures for ``--seconds`` and prints the
contract's JSON object as its last line. Without the cell's chips it exits
non-zero and prints no result.

``--rehearse`` is not a measurement: it runs the same control flow on the
CPU at the toy size of the files' ``rehearse`` blocks and says so in
``device.platform``; it reports no device metric.
"""

from __future__ import annotations

import time

T_PROC_WALL = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import families, traffic  # noqa: E402

GAP_NAMES = {"engine_step": "engine_step: host (sample, emit, admit)"}
GAP_OUTSIDE = {"serve": "between steps: engine loop waits for work",
               "train": "between steps: train loop on the host"}


def fail(msg: str, code: int = 2):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def merge(base, over):
    """``over``'s keys replace ``base``'s, dict by dict."""
    if not isinstance(base, dict) or not isinstance(over, dict):
        return over
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base.get(k), v)
    return out


def load_cell(bench_root: str, workload: str, rehearse: bool) -> dict:
    """The cell's entry with its configuration and traffic files read in;
    everything is found by the names in ``BENCHMARK.json``."""
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        fail(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(bench_root, conf["file"])) as f:
        config = json.load(f)
    # configs/, traffic/ and metrics/ sit side by side in one directory.
    bench_dir = os.path.dirname(os.path.dirname(
        os.path.join(bench_root, conf["file"])))
    mix = traffic.load(cell["traffic"], bench_dir)
    if rehearse:
        config = merge(config, config.get("rehearse", {}))
        mix = merge(mix, mix.get("rehearse", {}))

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {"name": workload, "chips": cell["chips"], "config": config,
            "traffic": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "metrics_dir": os.path.join(bench_dir, "metrics")}


def read_metric(metrics_dir: str, name: str, ctx: dict):
    """Run the reader ``metrics/<name>.py``; ``None`` leaves the metric
    out."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics._reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def leftover_processes(node_hex: str) -> list:
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            continue
        if node_hex in cmd and state != "Z":
            out.append(int(pid))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override one key of the traffic file (the knee "
                         "sweep sets rate_rps this way; never used by a "
                         "measured run)")
    ap.add_argument("--bench-root", default=ROOT,
                    help="directory of BENCHMARK.json (tests point it at a "
                         "copy)")
    args = ap.parse_args()
    cell = load_cell(args.bench_root, args.workload, args.rehearse)
    for item in args.set:
        key, _, value = item.partition("=")
        cell["traffic"][key] = json.loads(value)
    kind = cell["traffic"]["kind"]

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}")
    else:
        platforms = os.environ.get("JAX_PLATFORMS", "").lower()
        if platforms and "tpu" not in platforms.split(","):
            fail(f"JAX_PLATFORMS={platforms!r} keeps JAX off the accelerator;"
                 f" a measurement needs the chip (--rehearse is the CPU "
                 f"path, and it measures nothing)")
    # One compile cache at a fixed path inside the checkout, unless the
    # environment already names one; small and quick programs are kept too,
    # so that a second run compiles nothing at all.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    try:
        import ray_tpu
        from ray_tpu import serve, tpu
    except ImportError as e:
        fail(f"cannot import ray_tpu ({e}): run from a whole checkout")
    if not args.rehearse and not tpu.accelerator_device_files():
        fail("no accelerator: this machine has no TPU device files")

    if kind == "serve":
        # What the cell needs of the served architecture; a family that
        # only trains fails here, before the runtime starts.
        try:
            cell["serve"] = families.serve(cell["config"])
        except ValueError as e:
            fail(str(e))

    work_dir = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir, exist_ok=True)
    core = ray_tpu.init(num_cpus=8, **(
        {"resources": {"TPU": cell["chips"]}} if args.rehearse else {}))
    node_hex = core.node_id.hex()
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < cell["chips"]:
            fail(f"the cell needs {cell['chips']} chip(s); the probe found "
                 f"{have}")
        if kind == "serve":
            from benchmarks import serve_cell as runner
        else:
            from benchmarks import train_cell as runner
        try:
            ctx = runner.run(cell, args, T_PROC_WALL, work_dir)
        finally:
            if kind == "serve":
                serve.shutdown()
    finally:
        ray_tpu.shutdown()
    left = leftover_processes(node_hex)
    if left:
        fail(f"worker processes left behind: {left}")

    dev = ctx["device"]
    want_platform = "cpu" if args.rehearse else "tpu"
    if dev["platform"] != want_platform or dev["count"] != cell["chips"]:
        fail(f"the worker ran on {dev['platform']} x{dev['count']}, the cell "
             f"asks for {want_platform} x{cell['chips']}")
    line = {"correct": bool(ctx["correct"]), "attempted": ctx["attempted"],
            "failed": ctx["failed"], "metrics": {}, "device": dev}
    if not args.trace:
        for m in cell["end_to_end"]:
            line["metrics"][m["name"]] = {
                "value": ctx["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        ctx["trace"] = None
        if ctx.get("trace_dir") and not args.rehearse:
            from benchmarks import tracered

            reduced = tracered.reduce(
                tracered.load_xplane(ctx["trace_dir"]),
                outside=GAP_OUTSIDE[kind])
            if reduced is None:
                fail("the trace holds no device operation")
            ctx["trace"] = reduced
            dev["busy_s"], dev["window_s"] = (reduced["busy_s"],
                                              reduced["window_s"])
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in reduced["device_ops"]],
                "idle_gaps": [[GAP_NAMES.get(n, n), s]
                              for n, s in reduced["idle_gaps"]]}
        if kind == "serve":
            wall0 = ctx["marks"]["open_wall"]
            ctx["wall_window"] = (wall0, wall0 + args.seconds)
        for m in cell["per_layer"]:
            value = read_metric(cell["metrics_dir"], m["name"], ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
