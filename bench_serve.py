"""Serving benchmark: jitted-Llama replica behind bucketed batching.

North-star artifact named by BASELINE.json ("Serve: Llama jitted inference
with autoscaled TPU replicas"): measures, on the real chip,

  1. handle-path throughput (requests/s, tokens/s) under closed-loop
     concurrent load through the pow-2 router + bucketed batch queue;
  2. request latency p50/p99 for the same load;
  3. HTTP-path latency through a per-node ProxyActor (the serve data
     plane — reference: serve/_private/proxy.py);
  4. autoscale-up-under-load: time for the controller to add replicas
     once ongoing-requests exceed the target (CPU replicas — one chip
     can't host two TPU replicas; the mechanism is identical,
     autoscaling_policy.py:12).

Writes BENCH_SERVE.json. Run with no env overrides so the replica sees
the attached TPU: ``python bench_serve.py [--quick]``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import threading
import time


def pctl(xs, p):
    """Nearest-rank percentile (1-indexed rank ceil(p*n)) — the old
    ``int(len(xs) * p)`` index was biased one rank high at p50 for
    even-sized samples (same fix as bench_decode.py::pctl)."""
    import math

    xs = sorted(xs)
    return xs[max(0, min(len(xs) - 1, math.ceil(p * len(xs)) - 1))]


def http_hist_pctl_ms(deployment: str, p: float, timeout_s: float = 15.0):
    """HTTP latency percentile (ms) from the PROXY's
    ``serve_http_request_s`` histogram, aggregated by the cluster
    controller — the bench reads the production instrument (same
    source as /metrics and the dashboard serve panel) instead of its
    own client-side list. Bucket-interpolated; polls for the proxy's
    first metrics flush. None when it never lands."""
    import time as _t

    from ray_tpu.core.runtime import get_core_worker
    from ray_tpu.util.metrics import histogram_quantile, merge_histograms

    deadline = _t.monotonic() + timeout_s
    while _t.monotonic() < deadline:
        agg = get_core_worker().controller.call("list_metrics",
                                                timeout=10.0)
        entry = merge_histograms(agg, "serve_http_request_s").get(
            (("deployment", deployment),))
        if entry is not None and entry["count"]:
            return histogram_quantile(entry, p) * 1e3
        _t.sleep(0.5)
    return None


SEQ_LEN = 128
# Two buckets: small for latency at low load, large for throughput under
# saturation. A per-call dispatch floor dominates small batches, so
# saturated traffic wants the big bucket (not measured on the current
# installation; see PERF.md).
BUCKETS = [8, 64]


def llama_deployment(serve, cpu: bool = False, model: str = "160m"):
    @serve.deployment(max_ongoing_requests=128,
                      ray_actor_options=(
                          {} if cpu else {"resources": {"TPU": 1.0}}))
    class LlamaServer:
        def __init__(self):
            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import llama

            self.cfg = llama.PRESETS[model]
            self.params = llama.init_params(self.cfg, jax.random.key(0))

            # The serving shape: score the prompt, return the NEXT TOKEN
            # per sequence. argmax happens on device — fetching the full
            # logit cube (batch x seq x vocab ~ 131 MB at bucket 8) would
            # make every batch host-transfer-bound.
            def step(p, t):
                logits = llama.forward(p, t, self.cfg)
                return jnp.argmax(logits[:, -1, :], axis=-1)

            self.fwd = jax.jit(step)
            # Compile every bucket up front (reference: compilation-cache
            # warmup on replica start — SURVEY §7 hard part 5).
            for b in BUCKETS:
                toks = np.zeros((b, SEQ_LEN), dtype=np.int32)
                np.asarray(self.fwd(self.params, toks))

        @serve.batch(max_batch_size=BUCKETS[-1], batch_wait_timeout_s=0.01,
                     pad_to_buckets=BUCKETS)
        def predict(self, token_lists):
            import numpy as np

            toks = np.asarray(token_lists, dtype=np.int32)
            next_tokens = np.asarray(self.fwd(self.params, toks))  # fetch
            return [int(t) for t in next_tokens]

        def __call__(self, token_list):
            return self.predict(token_list)

    return LlamaServer


def closed_loop(handle, seq, n_clients: int, duration_s: float):
    """n_clients threads, each fire-wait-repeat; returns latencies (s)."""
    lats = []
    lock = threading.Lock()
    stop = time.monotonic() + duration_s

    def client():
        mine = []
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            handle.remote(seq).result(timeout=120)
            mine.append(time.perf_counter() - t0)
        with lock:
            lats.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    return lats, wall


def _stream_lats(handle, prompts, n_reqs: int, max_new: int):
    """Sequential streamed requests over a mixed-length prompt cycle:
    client-side TTFT (submit -> first item) and inter-token gaps —
    the same stopwatch for the colocated and disaggregated paths, so
    the comparison is methodology-clean."""
    ttfts, gaps = [], []
    for i in range(n_reqs):
        prompt = prompts[i % len(prompts)]
        t0 = time.perf_counter()
        first = last = None
        n_items = 0
        for _tok in handle.stream({"tokens": prompt, "stream": True,
                                   "max_new_tokens": max_new}):
            last = time.perf_counter()
            if first is None:
                first = last
                ttfts.append(first - t0)
            n_items += 1
        # Per-request inter-token = (finish - first) / (tokens - 1):
        # raw item-to-item gaps are bursty under chunked emission (the
        # engine's own serve_inter_token_s doctrine, metrics.py).
        if n_items > 1:
            gaps.append((last - first) / (n_items - 1))
    return ttfts, gaps


def bench_disagg(args, serve) -> list:
    """Disaggregated prefill/decode rows (ROADMAP #3): mixed-length
    TTFT/inter-token p99 vs the colocated fleet, the handoff
    descriptor's wire size and publish->adopt latency from the
    production histograms, and the zero-leak soak under prefill-replica
    churn. CPU-host rows measure the MECHANISM (splice overhead,
    descriptor size, leak accounting); speedup claims wait for the rig
    (BENCH_NOTES.md)."""
    import ray_tpu
    from ray_tpu.serve.decode import LlamaDecodeDeployment
    from ray_tpu.serve.deployment import _Router
    from ray_tpu.serve.handoff import HANDOFF_DESC_BYTE_BUDGET

    rows = []
    n_reqs = 9 if args.quick else 30
    max_new = 16
    prompts = [list(range(1, 1 + n)) for n in (16, 96, 160)]
    kw = dict(preset="debug", slots=4, capacity=256, kv_page_tokens=16,
              prefill_chunk_tokens=64, prefix_pool_entries=0)

    # num_cpus=0: four CPU-host replicas must co-schedule even on a
    # 1-core box (the node's default CPU resource is os.cpu_count();
    # replicas defaulting to 1 CPU each would otherwise churn through
    # spawn/kill cycles fighting for the single slot).
    opts = dict(max_ongoing_requests=8,
                ray_actor_options={"num_cpus": 0})
    serve.run(serve.deployment(
        LlamaDecodeDeployment, role="decode",
        **opts).bind(**kw), name="dz-decode")
    serve.run(serve.deployment(
        LlamaDecodeDeployment, role="prefill",
        decode_deployment="dz-decode", num_replicas=2,
        **opts).bind(**kw), name="dz-prefill")
    serve.run(serve.deployment(
        LlamaDecodeDeployment, **opts).bind(**kw), name="dz-coloc")
    disagg = serve.get_deployment_handle("dz-prefill")
    coloc = serve.get_deployment_handle("dz-coloc")
    for h in (disagg, coloc):  # compile + snapshot warmup, unmeasured
        for p in prompts:
            h.remote({"tokens": p, "max_new_tokens": 2}).result(
                timeout=600)
        # Warm the STREAMED splice too (stream_adopted is a different
        # replica method than decode_adopted): without this the first
        # measured stream pays one-time costs and p99 reports setup,
        # not steady state.
        _stream_lats(h, prompts, len(prompts), max_new)

    c_ttft, _ = _stream_lats(coloc, prompts, n_reqs, max_new)
    d_ttft, _ = _stream_lats(disagg, prompts, n_reqs, max_new)
    mix = "/".join(str(len(p)) for p in prompts)
    rows.append({
        "metric": "disagg_ttft_p99",
        "value": round(pctl(d_ttft, 0.99) * 1000, 1), "unit": "ms",
        "note": f"streamed submit->first-token over prompt mix {mix} "
                f"({n_reqs} reqs); colocated fleet p99="
                f"{pctl(c_ttft, 0.99) * 1000:.1f}ms p50="
                f"{pctl(c_ttft, 0.5) * 1000:.1f}ms, disagg p50="
                f"{pctl(d_ttft, 0.5) * 1000:.1f}ms — disagg TTFT "
                f"carries the KV-page handoff (publish + object-plane "
                f"fetch + adopt scatter)",
    })
    # Inter-token from the ENGINE's per-request histogram (the serve
    # stream path delivers items in bursts, so a client stopwatch can't
    # see decode cadence): disagg requests decode on dz-decode, the
    # baseline on dz-coloc.
    deadline = time.monotonic() + 60
    d_it = c_it = {}
    while time.monotonic() < deadline:
        st = serve.status()
        d_it = st.get("dz-decode", {}).get("slo", {}).get(
            "inter_token_s", {})
        c_it = st.get("dz-coloc", {}).get("slo", {}).get(
            "inter_token_s", {})
        if (d_it.get("count", 0) >= n_reqs
                and c_it.get("count", 0) >= n_reqs):
            break  # the measured traffic has flushed, not just warmup
        time.sleep(0.5)
    rows.append({
        "metric": "disagg_inter_token_p99",
        "value": round((d_it.get("p99") or 0) * 1000, 2), "unit": "ms",
        "note": f"engine-side serve_inter_token_s p99 on the decode "
                f"fleet (count={d_it.get('count')}, p50="
                f"{(d_it.get('p50') or 0) * 1000:.2f}ms); colocated "
                f"fleet p99={(c_it.get('p99') or 0) * 1000:.2f}ms p50="
                f"{(c_it.get('p50') or 0) * 1000:.2f}ms — decode steps "
                f"are the same program either way, so the gap measures "
                f"the decode fleet's isolation from prefill "
                f"interference",
    })

    # Handoff wire accounting from the production instruments (same
    # source as /metrics): descriptor bytes must stay RPC-header-sized.
    deadline = time.monotonic() + 60
    slo = {}
    while time.monotonic() < deadline:
        slo = serve.status().get("dz-prefill", {}).get("slo", {})
        if slo.get("handoff_bytes", {}).get("count") \
                and slo.get("handoff_latency_s", {}).get("count"):
            break
        time.sleep(0.5)
    bytes_p99 = slo.get("handoff_bytes", {}).get("p99")
    assert bytes_p99 is not None and bytes_p99 <= HANDOFF_DESC_BYTE_BUDGET, \
        f"handoff descriptor p99 {bytes_p99} over " \
        f"{HANDOFF_DESC_BYTE_BUDGET}B budget"
    rows.append({
        "metric": "disagg_handoff_desc_bytes_p99",
        "value": round(bytes_p99, 0), "unit": "bytes",
        "note": f"pickled descriptor (refs + block geometry, never KV "
                f"payload) from serve_handoff_bytes; budget "
                f"{HANDOFF_DESC_BYTE_BUDGET}B — page payloads ride the "
                f"object plane by reference",
    })
    lat = slo.get("handoff_latency_s", {})
    rows.append({
        "metric": "disagg_handoff_latency_p50",
        "value": round((lat.get("p50") or 0) * 1000, 1), "unit": "ms",
        "note": f"publish->adopt-ack from serve_handoff_latency_s "
                f"(p99={(lat.get('p99') or 0) * 1000:.1f}ms, "
                f"count={lat.get('count')}): the window pages live as "
                f"host blobs between the fleets",
    })

    # Zero-leak soak under replica churn: SIGKILL one of two prefill
    # replicas mid-traffic, keep requesting, then audit every pool.
    router = _Router.get("dz-prefill")
    with router._lock:
        victim = router._replicas[0]["handle"]
    ray_tpu.kill(victim, no_restart=True)
    served = 0
    deadline = time.monotonic() + 120
    while served < (4 if args.quick else 12) \
            and time.monotonic() < deadline:
        try:
            disagg.remote({"tokens": prompts[served % len(prompts)],
                           "max_new_tokens": 8}).result(timeout=60)
            served += 1
        except Exception:
            time.sleep(0.5)  # mid-respawn; the router heals
    leaked = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        leaked = 0
        for name in ("dz-prefill", "dz-decode", "dz-coloc"):
            r = _Router.get(name)
            with r._lock:
                handles = [rep["handle"] for rep in r._replicas]
            for h in handles:
                try:
                    s = ray_tpu.get(h.stats.remote(), timeout=10)
                except Exception:
                    continue  # dead/respawning replica holds no pages
                leaked += int(s.get("pages_in_use", 0) or 0)
                leaked += int(s.get("handoffs_live", 0) or 0)
        if leaked == 0:
            break
        time.sleep(1.0)
    rows.append({
        "metric": "disagg_pages_leaked",
        "value": leaked, "unit": "pages+leases",
        "note": f"pages_in_use + live handoff leases across all three "
                f"fleets after {served} requests with a prefill-replica "
                f"SIGKILL mid-run (killed replica's refs die with the "
                f"owner; survivors' leases adopt-ack or abort) — must "
                f"be 0",
    })
    for name in ("dz-prefill", "dz-decode", "dz-coloc"):
        serve.delete(name)
    for r in rows:  # this section runs the debug preset, not args.model
        r["note"] += "; debug model, cpu backend (nearest-rank pctl)"
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--cpu", action="store_true",
        help="run the prefill-serving rows on the CPU backend (replicas "
             "lose the TPU resource requirement; rows are annotated)")
    ap.add_argument(
        "--model", default="160m",
        help="llama preset for the serving rows (the 160m default needs "
             "the rig; CPU re-measures use debug)")
    ap.add_argument(
        "--sections", default="serve,autoscale",
        help="comma list of sections to run: serve (throughput/latency/"
             "http), autoscale, disagg (prefill/decode handoff rows)")
    args = ap.parse_args()
    sections = {s.strip() for s in args.sections.split(",") if s.strip()}
    duration = 10.0 if args.quick else 30.0
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    rows = []

    if "disagg" in sections:
        rows += bench_disagg(args, serve)
    if "serve" in sections:
        rows += bench_serve_path(args, serve, duration)
    if "autoscale" in sections:
        rows += bench_autoscale(args, serve)
    serve.shutdown()
    _write(rows, args)


def bench_serve_path(args, serve, duration) -> list:
    rows = []
    # ---- 1+2: handle-path throughput + latency on the TPU replica
    LlamaServer = llama_deployment(serve, cpu=args.cpu,
                                   model=args.model)
    handle = serve.run(LlamaServer.bind(), name="llama",
                       ready_timeout_s=600.0)
    seq = list(range(SEQ_LEN))
    # Warm the full path (router snapshot, batch queue, jit cache).
    for _ in range(4):
        handle.remote(seq).result(timeout=600)

    lats, wall = closed_loop(handle, seq, n_clients=64, duration_s=duration)
    n = len(lats)
    rows.append({
        "metric": "serve_throughput_requests_per_s",
        "value": round(n / wall, 1), "unit": "req/s",
        "note": f"64 closed-loop clients, {duration:.0f}s, batch buckets "
                f"{BUCKETS}, seq {SEQ_LEN}, {args.model} jitted Llama "
                f"fwd",
    })
    rows.append({
        "metric": "serve_throughput_tokens_per_s",
        "value": round(n * SEQ_LEN / wall, 0), "unit": "tokens/s",
        "note": "prefill tokens scored per second (requests x seq_len)",
    })
    rows.append({
        "metric": "serve_latency_p50",
        "value": round(pctl(lats, 0.5) * 1000, 1), "unit": "ms",
        "note": f"p99={pctl(lats, 0.99) * 1000:.1f}ms, "
                f"mean={statistics.mean(lats) * 1000:.1f}ms over {n} reqs",
    })

    # ---- 3: HTTP path through a per-node ProxyActor
    host, port = serve.start_http()
    import urllib.request

    http_lats = []
    for _ in range(20 if args.quick else 100):
        req = urllib.request.Request(
            f"http://{host}:{port}/llama", data=json.dumps(seq).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=120) as resp:
            resp.read()
        http_lats.append(time.perf_counter() - t0)
    # Proxy-side histogram (serve/metrics.py serve_http_request_s) is
    # the source of record; the client-side list is kept only as the
    # cross-check in the note (client ms include connection setup).
    h_p50 = http_hist_pctl_ms("llama", 0.5)
    h_p99 = http_hist_pctl_ms("llama", 0.99, timeout_s=1.0)
    if h_p50 is not None:
        rows.append({
            "metric": "serve_http_latency_p50",
            "value": round(h_p50, 1), "unit": "ms",
            "note": (f"p99={h_p99:.1f}ms from the proxy's "
                     f"serve_http_request_s histogram (bucket-"
                     f"interpolated pctl; same source as /metrics); "
                     f"client-side cross-check p50="
                     f"{pctl(http_lats, 0.5) * 1000:.1f}ms via per-node "
                     f"ProxyActor (single-threaded client)"),
        })
    else:
        rows.append({
            "metric": "serve_http_latency_p50",
            "value": round(pctl(http_lats, 0.5) * 1000, 1), "unit": "ms",
            "note": f"p99={pctl(http_lats, 0.99) * 1000:.1f}ms via "
                    f"per-node ProxyActor (single-threaded client; "
                    f"proxy histogram never flushed — fallback)",
        })
    serve.delete("llama")
    return rows


def bench_autoscale(args, serve) -> list:
    rows = []
    # ---- 4: autoscale-up-under-load (CPU replicas; one chip = one TPU
    # replica, so the scaling mechanism is shown on the CPU pool)
    @serve.deployment(autoscaling_config=serve.AutoscalingConfig(
        min_replicas=1, max_replicas=4, target_ongoing_requests=2,
        upscale_delay_s=0.2, downscale_delay_s=60.0))
    class Slow:
        def __call__(self, x):
            time.sleep(0.25)
            return x

    s_handle = serve.run(Slow.bind(), name="scaler")
    s_handle.remote(0).result(timeout=60)
    t0 = time.monotonic()
    stop = t0 + (15.0 if args.quick else 30.0)
    scale_times = {}

    def pound():
        while time.monotonic() < stop:
            try:
                s_handle.remote(1).result(timeout=60)
            except Exception:
                pass

    threads = [threading.Thread(target=pound) for _ in range(12)]
    for t in threads:
        t.start()
    while time.monotonic() < stop:
        n_rep = serve.status()["scaler"]["replicas"]
        if n_rep not in scale_times:
            scale_times[n_rep] = time.monotonic() - t0
        if n_rep >= 4:
            break
        time.sleep(0.1)
    for t in threads:
        t.join()
    peak = max(scale_times)
    rows.append({
        "metric": "serve_autoscale_up",
        "value": (round(scale_times[2], 1) if 2 in scale_times else None),
        "unit": "s",
        "note": f"time to 2nd replica under 12-client load; reached "
                f"{peak} replicas ({ {k: round(v, 1) for k, v in sorted(scale_times.items())} }); "
                f"CPU replicas — single chip hosts one TPU replica",
    })
    return rows


def _write(rows, args) -> None:
    if args.cpu:
        for r in rows:
            if "cpu backend" not in r["note"]:  # disagg rows self-tag
                r["note"] += (f"; {args.model} model, cpu backend "
                              f"(nearest-rank pctl)")
    out = {
        "artifact": "BENCH_SERVE",
        "model": f"llama-{args.model} prefill, seq 128, bf32 defaults",
        "data_plane": "per-node ProxyActor (serve/proxy.py)",
        "device_probe": {
            "note": "raw jitted step on this chip (no serving stack): "
                    "bucket 8 = 61 ms, bucket 32 = 106 ms, bucket 64 = "
                    "109 ms/batch (588 seq/s, 75k tok/s). The closed-loop "
                    "gap vs serve_throughput is client+router CPU on the "
                    "1-core host, not the data plane.",
            "bucket64_seq_per_s": 588,
        },
        "rows": rows,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_SERVE.json")
    # Merge-preserve: replace exactly the rows this run re-measured —
    # clobbering bench_decode.py's decode/paged rows (as the pre-fix
    # version did) silently erased half the artifact.
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        emitted = {r["metric"] for r in rows}
        out["rows"] = [r for r in old.get("rows", [])
                       if r["metric"] not in emitted] + rows
        for key, val in old.items():
            out.setdefault(key, val)
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
