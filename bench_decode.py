"""Decode serving benchmark: KV-cache generation rows for BENCH_SERVE.json
(VERDICT r4 Next #3 — "Re-measure BENCH_SERVE with decode tokens/s and
per-token p50").

Measures on the attached chip, 160M-param Llama:

  1. engine-direct continuous batching (slots=16): decode tokens/s,
     inter-token p50/p99, TTFT p50 — per-token steps (decode_chunk=1);
  2. same with decode_chunk=8 (K greedy steps per device call): the
     dispatch-floor amortization row;
  3. the full serve stack: deployment replica + handle, closed-loop
     clients requesting generation (streamed tokens).

Appends/replaces the decode rows in BENCH_SERVE.json, preserving the
prefill rows. Run: ``python bench_decode.py [--quick]``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import threading
import time

from ray_tpu.util import compile_cache

compile_cache.configure()


def pctl(xs, p):
    """Nearest-rank percentile: the value at 1-indexed rank ceil(p*n).
    The old ``int(len(xs) * p)`` index was biased one rank high (p50 of
    an even-sized sample read above the median; p100 depended on the
    min() clamp), which skews small-sample p50/p99 rows."""
    xs = sorted(xs)
    return xs[max(0, min(len(xs) - 1, math.ceil(p * len(xs)) - 1))]


def hist_pctl_ms(deployment: str, metric: str, p: float,
                 aggregated=None):
    """Percentile (ms) of a serve SLO histogram for one deployment —
    the bench reads the SAME instruments production scrapes instead of
    keeping its own ad-hoc latency lists. Values are bucket-
    interpolated (Prometheus histogram_quantile semantics), so they
    are quantized to the bucket grid. ``aggregated=None`` reads this
    process's registry; pass a ``list_metrics`` result for
    cluster-side (replica) histograms."""
    from ray_tpu.util.metrics import _Registry, histogram_quantile, \
        merge_histograms

    if aggregated is None:
        aggregated = {"local": _Registry.get().snapshot()}
    merged = merge_histograms(aggregated, metric)
    entry = merged.get((("deployment", deployment),))
    if entry is None or not entry["count"]:
        return None
    return histogram_quantile(entry, p) * 1e3


def engine_rows(params, cfg, quick: bool, platform: str = ""):
    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    slots = 4 if quick else 16
    prompt_len = 16 if quick else 64
    gen = 16 if quick else 64
    n_requests = 8 if quick else 64
    rows = []
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    for chunk in (1, 8):
        # Prefix cache off: this workload is zero-share random prompts
        # (every insert would be futile) — the shared_prefix section is
        # the one that measures the cache.
        eng = DecodeEngine(params, cfg, slots=slots,
                           capacity=256, decode_chunk=chunk,
                           prefix_pool_entries=0,
                           metrics_deployment=f"warmup_chunk{chunk}")
        # Warm every program before timing: each admission batch size
        # (n = 1..slots, powers of two), the decode step, and (for
        # chunked mode) the whole k ladder — a solo request's
        # remaining-count walks down through all of k=chunk..1.
        w = eng.submit(prompts[0], max_new_tokens=max(2, 2 * chunk))
        while not w.done.is_set():
            eng.step()
        n_warm = 2
        while n_warm <= slots:
            burst = [eng.submit(prompts[i % len(prompts)],
                                max_new_tokens=1) for i in range(n_warm)]
            while not all(b.done.is_set() for b in burst):
                eng.step()
            n_warm *= 2

        # Warmup compiles recorded under warmup_chunk*; the measured
        # requests observe under the row's own label (terminal-step
        # labeling), so compile time never skews the percentile rows.
        eng.set_metrics_deployment(f"bench_chunk{chunk}")
        t0 = time.monotonic()
        reqs = [eng.submit(p, max_new_tokens=gen)
                for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            if eng.step() == 0:
                time.sleep(0.001)
        wall = time.monotonic() - t0
        total_tokens = sum(len(r.output) for r in reqs)
        # Percentiles from the serve SLO HISTOGRAMS the engine records
        # (serve/metrics.py: inter-token = per-request stream duration
        # / token, robust to chunked emission's bursts) — the bench
        # reads the production instruments instead of ad-hoc lists, so
        # a bench row and a Prometheus scrape can never disagree.
        dep = f"bench_chunk{chunk}"
        tok_p50 = hist_pctl_ms(dep, "serve_inter_token_s", 0.5)
        tok_p99 = hist_pctl_ms(dep, "serve_inter_token_s", 0.99)
        ttft_p50 = hist_pctl_ms(dep, "serve_ttft_s", 0.5)
        rows.append({
            "metric": f"decode_tokens_per_s_chunk{chunk}",
            "value": round(total_tokens / wall, 1),
            "unit": "tokens/s",
            "note": (f"{n_requests} reqs x {gen} new tokens, prompt "
                     f"{prompt_len}, {slots} slots continuous batching, "
                     f"decode_chunk={chunk}; wall {wall:.1f}s; "
                     f"{platform}"),
        })
        rows.append({
            "metric": f"decode_per_token_p50_chunk{chunk}",
            "value": round(tok_p50, 1) if tok_p50 is not None else None,
            "unit": "ms",
            "note": (f"per-request stream duration/token; p99="
                     f"{tok_p99:.1f}ms; TTFT p50={ttft_p50:.0f}ms "
                     f"(includes queueing — {n_requests} reqs over "
                     f"{slots} slots); from serve_inter_token_s/"
                     f"serve_ttft_s histograms (bucket-interpolated "
                     f"pctl); {platform}"
                     if tok_p50 is not None else ""),
        })
        eng.shutdown()
    return rows


def shared_prefix_rows(params, cfg, quick: bool, platform: str):
    """Shared-prefix workload (hot system prompt): TTFT with the prefix
    KV cache off vs on, plus hit rate and prefill tokens saved. Models
    RLAX-style rollout generation / templated chat traffic where >=50%
    of every prompt is a shared prefix."""
    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    # shared_len sits ON the power-of-two insert grid so the pool entry
    # covers exactly the shared region (prefix_capacity = capacity//2).
    slots = 4 if quick else 8
    shared_len = 32 if quick else 128
    suffix_len = 12 if quick else 32
    gen = 4 if quick else 4
    n_requests = 8 if quick else 32
    capacity = 128 if quick else 256
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, shared_len).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size,
                                     suffix_len).tolist()
               for _ in range(n_requests)]
    prompt_len = shared_len + suffix_len

    results = {}
    for mode, entries in (("off", 0), ("on", 8)):
        eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                           prefix_pool_entries=entries,
                           prefix_match_min_tokens=16)
        # Warm every program (admission n ladder, both prefill paths,
        # decode step) AND the prefix pool itself: the row measures
        # steady-state serving of a hot prefix, not the cold insert.
        w = eng.submit(prompts[0], max_new_tokens=2)
        while not w.done.is_set():
            eng.step()
        n_warm = 1
        while n_warm <= slots:
            burst = [eng.submit(prompts[i % len(prompts)],
                                max_new_tokens=1) for i in range(n_warm)]
            while not all(b.done.is_set() for b in burst):
                eng.step()
            n_warm *= 2
        pre = eng.prefix.stats() if eng.prefix is not None else None

        reqs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            if eng.step() == 0:
                time.sleep(0.001)
        ttfts = [1e3 * (r.first_token_at - r.submitted_at) for r in reqs]
        stats = {"p50": pctl(ttfts, 0.5), "p99": pctl(ttfts, 0.99)}
        if pre is not None:
            post = eng.prefix.stats()
            queries = post["queries"] - pre["queries"]
            hits = post["hits"] - pre["hits"]
            stats["hit_rate"] = hits / max(1, queries)
            stats["tokens_saved"] = (post["prefill_tokens_saved"]
                                     - pre["prefill_tokens_saved"])
        eng.shutdown()
        results[mode] = stats

    speedup = results["off"]["p50"] / max(1e-9, results["on"]["p50"])
    workload = (f"{n_requests} reqs, prompt {prompt_len} "
                f"({shared_len} shared / {100 * shared_len // prompt_len}%"
                f"), {gen} new tokens, {slots} slots; {platform}")
    return [
        {
            "metric": "decode_shared_prefix_ttft_p50_off",
            "value": round(results["off"]["p50"], 1),
            "unit": "ms",
            "note": (f"prefix cache OFF; p99="
                     f"{results['off']['p99']:.1f}ms; {workload}"),
        },
        {
            "metric": "decode_shared_prefix_ttft_p50_on",
            "value": round(results["on"]["p50"], 1),
            "unit": "ms",
            "note": (f"prefix cache ON (suffix-only prefill); p99="
                     f"{results['on']['p99']:.1f}ms; {speedup:.1f}x TTFT "
                     f"p50 vs off; {workload}"),
        },
        {
            "metric": "decode_prefix_hit_rate",
            "value": round(100 * results["on"]["hit_rate"], 1),
            "unit": "%",
            "note": (f"prefix-cache hits / admissions over the timed "
                     f"workload (warm pool); {workload}"),
        },
        {
            "metric": "decode_prefix_prefill_tokens_saved",
            "value": int(results["on"]["tokens_saved"]),
            "unit": "tokens",
            "note": (f"prompt tokens spliced from the prefix pool "
                     f"instead of re-prefilled; {workload}"),
        },
    ]


def overload_rows(params, cfg, quick: bool, platform: str):
    """Load-shedding behavior at 2x slot capacity (ISSUE 3):
    ``2 * slots`` closed-loop clients against a small pending-queue cap,
    vs a ``slots``-client non-overloaded baseline measured the same way.
    Shed clients honor ``Retry-After`` (bounded). The cap is deliberately
    tight (``max(1, slots // 4)``): under sustained overload ANY queue
    depth converts straight into accepted-request TTFT (Little's law),
    so the engine sheds the excess in <1 ms and keeps the queue — and
    therefore accepted latency — short. Rows record shed-rejection p99
    (bar: < 50 ms), accepted TTFT p99 vs baseline (bar: < 1.5x), and the
    max observed queue depth (bar: never exceeds queue_max)."""
    import threading

    from ray_tpu.core.errors import OverloadedError
    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    slots = 4 if quick else 8
    prompt_len = 16 if quick else 32
    gen = 8 if quick else 16
    duration = 6.0 if quick else 25.0
    queue_max = max(1, slots // 8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(4 * slots)]

    eng = DecodeEngine(params, cfg, slots=slots, capacity=128,
                       prefix_pool_entries=0, queue_max=4 * slots)
    # Warm the program ladder (loose cap: warm bursts queue up before
    # the manual step loop drains them).
    w = eng.submit(prompts[0], max_new_tokens=2)
    while not w.done.is_set():
        eng.step()
    n_warm = 2
    while n_warm <= slots:
        burst = [eng.submit(prompts[i], max_new_tokens=1)
                 for i in range(n_warm)]
        while not all(b.done.is_set() for b in burst):
            eng.step()
        n_warm *= 2
    eng.queue_max = queue_max  # the measured configuration

    loop = threading.Thread(target=eng.serve_forever, daemon=True)
    loop.start()

    def run_phase(n_clients: int, phase_s: float):
        ttfts: list = []
        sheds: list = []
        stop = time.monotonic() + phase_s
        max_queue = [0]

        def client(ci: int) -> None:
            # Varied generation lengths (gen/2 .. 3*gen/2): equal
            # lengths complete in synchronized waves, which makes every
            # queued request wait a FULL generation for a slot — an
            # artifact no real traffic mix has.
            crng = np.random.default_rng(100 + ci)
            while time.monotonic() < stop:
                t0 = time.perf_counter()
                n_new = int(crng.integers(max(1, gen // 2),
                                          gen + gen // 2 + 1))
                try:
                    req = eng.submit(prompts[ci % len(prompts)],
                                     max_new_tokens=n_new)
                except OverloadedError as e:
                    sheds.append(1e3 * (time.perf_counter() - t0))
                    time.sleep(min(e.retry_after_s, 0.25))
                    continue
                req.done.wait()
                if req.first_token_at is not None:
                    ttfts.append(1e3 * (req.first_token_at
                                        - req.submitted_at))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            max_queue[0] = max(max_queue[0], eng.stats()["queued"])
            time.sleep(0.01)
        return ttfts, sheds, max_queue[0]

    base_ttft, _, _ = run_phase(slots, duration)
    accepted, shed_lat, max_queue = run_phase(2 * slots, duration)
    eng.shutdown()
    loop.join(timeout=5)

    workload = (f"closed-loop {2 * slots} clients / {slots} slots for "
                f"{duration:.0f}s, queue_max={queue_max}, prompt "
                f"{prompt_len}, {gen} new tokens; {platform}")
    base_p99 = pctl(base_ttft, 0.99) if base_ttft else float("nan")
    acc_p99 = pctl(accepted, 0.99) if accepted else None
    return [
        {
            "metric": "decode_overload_shed_rejection_p99",
            "value": round(pctl(shed_lat, 0.99), 3) if shed_lat else None,
            "unit": "ms",
            "note": (f"submit()->OverloadedError latency over "
                     f"{len(shed_lat)} shed requests (p50="
                     f"{pctl(shed_lat, 0.5):.3f}ms); bar <50ms; "
                     f"{workload}" if shed_lat else workload),
        },
        {
            "metric": "decode_overload_accepted_ttft_p99",
            "value": round(acc_p99, 1) if acc_p99 is not None else None,
            "unit": "ms",
            "note": (f"TTFT p99 of {len(accepted)} ACCEPTED requests at "
                     f"2x offered load = "
                     f"{acc_p99 / max(1e-9, base_p99):.2f}x the "
                     f"non-overloaded closed-loop baseline p99 "
                     f"({base_p99:.1f}ms, {len(base_ttft)} reqs); max "
                     f"pending-queue depth observed {max_queue} (cap "
                     f"{queue_max}); {workload}"
                     if acc_p99 is not None else workload),
        },
    ]


def paged_rows(quick: bool, platform: str):
    """Paged-KV rows (ISSUE 6): (a) concurrency per pool byte — active
    requests sustained in the same pool bytes vs whole-row capacity
    (acceptance bar >= 1.5x, also asserted in tests/test_paged_kv.py);
    (b) mixed 64/512/4k prompt mix, chunked-prefill ON vs OFF: TTFT p99
    and per-token p99 (the un-chunked baseline is one monolithic prefill
    per admission — every active stream stalls for its duration);
    (c) tokens/s/slot and HBM pool bytes per active request.

    Uses a dedicated small config with a long rope table (the preset
    debug model caps max_seq_len at 128; 4k prompts need 8k)."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    cfg = llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=2048 if quick else 8192)
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    T = 64
    rows = []

    # ---- (a) + (c): overcommitted pool concurrency, same pool bytes
    slots, capacity, pool_pages = 16, 1024, 8 * 1024 // T
    whole_rows = pool_pages * T // capacity  # 8
    eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                       page_tokens=T, pool_pages=pool_pages,
                       prefix_pool_entries=0)
    pool_bytes = int(eng.cache["k"].nbytes + eng.cache["v"].nbytes)
    prompts = [rng.integers(0, cfg.vocab_size, 70).tolist()
               for _ in range(slots)]
    warm = [eng.submit(p, max_new_tokens=2) for p in prompts]
    while not all(w.done.is_set() for w in warm):
        eng.step()
    t0 = time.monotonic()
    gen = 16 if quick else 48
    reqs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
    eng.step()
    active = eng.stats()["active"]
    while not all(r.done.is_set() for r in reqs):
        eng.step()
    wall = time.monotonic() - t0
    total = sum(len(r.output) for r in reqs)
    rows.append({
        "metric": "decode_paged_concurrency_gain",
        "value": round(active / whole_rows, 2),
        "unit": "x",
        "note": (f"{active} concurrent active requests in a pool whose "
                 f"bytes hold {whole_rows} whole {capacity}-token rows "
                 f"(kv_page_tokens={T}, {pool_pages} pages, "
                 f"{pool_bytes / 1e6:.1f} MB pool); bar >= 1.5x; "
                 f"prompt 70 + {gen} new; {platform}"),
    })
    rows.append({
        "metric": "decode_paged_pool_bytes_per_request",
        "value": round(pool_bytes / active / 1e6, 3),
        "unit": "MB",
        "note": (f"KV pool bytes / {active} active requests (whole-row "
                 f"equivalent: {pool_bytes / whole_rows / 1e6:.3f} MB); "
                 f"{platform}"),
    })
    rows.append({
        "metric": "decode_paged_tokens_per_s_per_slot",
        "value": round(total / wall / active, 2),
        "unit": "tokens/s/slot",
        "note": (f"{total} tokens over {wall:.1f}s across {active} "
                 f"paged slots (tiny 2-layer model; the row tracks the "
                 f"paged-vs-whole-row regression, not absolute speed); "
                 f"{platform}"),
    })
    eng.shutdown()

    # ---- (b): mixed prompt mix, chunked prefill ON vs OFF
    mix = ([32, 32, 128, 128, 512] if quick
           else [64, 64, 64, 512, 512, 4096])
    gen = 8 if quick else 24
    capacity = 1024 if quick else 4352  # 4096 + headroom, % 64 == 0
    chunk = 128 if quick else 256
    results = {}
    for mode, chunk_tok in (("monolithic", 0), ("chunked", chunk)):
        eng = DecodeEngine(params, cfg, slots=4, capacity=capacity,
                           page_tokens=T, prefix_pool_entries=0,
                           prefill_chunk_tokens=chunk_tok)
        # Warm every program in the mix (compile outside the window).
        warm = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                           max_new_tokens=2) for n in set(mix)]
        while not all(w.done.is_set() for w in warm):
            eng.step()
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in mix]
        t0 = time.monotonic()
        reqs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            if eng.step() == 0:
                time.sleep(0.001)
        wall = time.monotonic() - t0
        ttfts = [1e3 * (r.first_token_at - r.submitted_at) for r in reqs]
        per_tok = [1e3 * (r.finished_at - r.first_token_at)
                   / max(1, len(r.output) - 1) for r in reqs
                   if len(r.output) > 1]
        results[mode] = {
            "ttft_p99": pctl(ttfts, 0.99),
            "per_tok_p99": pctl(per_tok, 0.99),
            "wall": wall,
            "chunks": eng.prefill_chunks,
        }
        eng.shutdown()
    workload = (f"{len(mix)} reqs, prompt mix {sorted(set(mix))}, "
                f"{gen} new tokens, 4 paged slots (T={T}); {platform}")
    rows.append({
        "metric": "decode_paged_mixed_ttft_p99_monolithic",
        "value": round(results["monolithic"]["ttft_p99"], 1),
        "unit": "ms",
        "note": (f"chunked prefill OFF (one monolithic prefill per "
                 f"admission); per-token p99="
                 f"{results['monolithic']['per_tok_p99']:.1f}ms; "
                 f"{workload}"),
    })
    rows.append({
        "metric": "decode_paged_mixed_ttft_p99_chunked",
        "value": round(results["chunked"]["ttft_p99"], 1),
        "unit": "ms",
        "note": (f"chunked prefill ON (prefill_chunk_tokens={chunk}, "
                 f"{results['chunked']['chunks']} chunks interleaved); "
                 f"per-token p99="
                 f"{results['chunked']['per_tok_p99']:.1f}ms vs "
                 f"{results['monolithic']['per_tok_p99']:.1f}ms "
                 f"un-chunked — a long admission stalls active streams "
                 f"for at most one chunk; {workload}"),
    })
    rows.append({
        "metric": "decode_paged_mixed_per_token_p99_chunked",
        "value": round(results["chunked"]["per_tok_p99"], 1),
        "unit": "ms",
        "note": (f"inter-token p99 of ACTIVE streams while 4k-class "
                 f"prefills interleave (un-chunked baseline "
                 f"{results['monolithic']['per_tok_p99']:.1f}ms); "
                 f"{workload}"),
    })
    return rows


def spec_rows(quick: bool, platform: str):
    """Speculative-decoding rows (ISSUE 16): accept-rate x tokens/s per
    prompt mix, bracketed by the two draft extremes reachable with
    random weights — a SELF-draft (the target proposes for itself, so
    acceptance ~= 1.0 and the row isolates the verify-batching /
    dispatch-amortization ceiling) and a tiny independent draft
    (acceptance ~= 0 on random weights: the pure-overhead floor). A
    trained draft lands between the brackets. Sampled (temp 0.8) rows
    measure the documented fallback: spec disengages (argmax acceptance
    rule) and the fused device sampler carries the batch. Plus the
    donated-buffer / device-sampler step-time delta row. CPU-host
    caveats: BENCH_NOTES.md, "Speculative decoding (PR 16)"."""
    import jax
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    cfg = llama.LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=2048 if quick else 8192)
    params = llama.init_params(cfg, jax.random.key(0))
    draft_cfg = llama.LlamaConfig(
        vocab_size=256, dim=32, n_layers=1, n_heads=2, n_kv_heads=1,
        mlp_dim=64, max_seq_len=cfg.max_seq_len)
    draft_params = llama.init_params(draft_cfg, jax.random.key(1))
    rng = np.random.default_rng(0)
    T, slots, k = 64, 4, 4
    gen = 12 if quick else 32
    mixes = [("64", 64), ("512", 512)]
    if not quick:
        mixes.append(("4k", 4096))
    rows = []

    def run(mix_len, temperature=0.0, draft=None, sampler=False):
        capacity = 1 << (mix_len + gen + k + 1).bit_length()
        capacity = min(capacity, cfg.max_seq_len)
        kw = dict(page_tokens=T,
                  pool_pages=slots * (capacity // T) + 1,
                  prefix_pool_entries=0, device_sampler=sampler)
        if draft is not None:
            kw.update(spec_draft_params=draft[0],
                      spec_draft_config=draft[1], spec_k=k)
        eng = DecodeEngine(params, cfg, slots=slots,
                           capacity=capacity, **kw)
        prompts = [rng.integers(0, cfg.vocab_size, mix_len).tolist()
                   for _ in range(slots)]
        warm = [eng.submit(p, max_new_tokens=2,
                           temperature=temperature) for p in prompts]
        while not all(w.done.is_set() for w in warm):
            eng.step()
        t0 = time.monotonic()
        reqs = [eng.submit(p, max_new_tokens=gen,
                           temperature=temperature) for p in prompts]
        while not all(r.done.is_set() for r in reqs):
            eng.step()
        wall = time.monotonic() - t0
        st = eng.stats()
        eng.shutdown()
        total = sum(len(r.output) for r in reqs)
        sp = st.get("spec") or {}
        return total / wall, sp.get("accept_rate")

    for name, mix_len in mixes:
        base_tps, _ = run(mix_len)
        self_tps, self_ar = run(mix_len, draft=(params, cfg))
        tiny_tps, tiny_ar = run(mix_len, draft=(draft_params, draft_cfg))
        rows.append({
            "metric": f"decode_spec_accept_rate_{name}",
            "value": round(self_ar or 0.0, 3),
            "unit": "accepted/proposed",
            "note": (f"greedy, k={k}, self-draft bracket (tiny random "
                     f"draft floor: {tiny_ar}); prompt {mix_len} + "
                     f"{gen} new x {slots} slots; {platform}"),
        })
        rows.append({
            "metric": f"decode_spec_tokens_per_s_{name}",
            "value": round(self_tps, 2),
            "unit": "tokens/s",
            "note": (f"greedy spec engine tokens/s at the self-draft "
                     f"bracket ({self_tps / base_tps:.2f}x plain "
                     f"{base_tps:.1f}; tiny-draft floor "
                     f"{tiny_tps:.1f} = {tiny_tps / base_tps:.2f}x); "
                     f"k={k}; {platform}"),
        })
        samp_tps, _ = run(mix_len, temperature=0.8,
                          draft=(params, cfg), sampler=True)
        rows.append({
            "metric": f"decode_spec_sampled_tokens_per_s_{name}",
            "value": round(samp_tps, 2),
            "unit": "tokens/s",
            "note": (f"temp 0.8 mix on the SAME spec-configured "
                     f"engine: spec disengages (argmax acceptance "
                     f"rule), fused device sampler carries the batch "
                     f"({samp_tps / base_tps:.2f}x the greedy plain "
                     f"path); {platform}"),
        })

    # ---- donated-buffer + device-sampler step-time delta (512 mix)
    host_tps, _ = run(512, temperature=0.8, sampler=False)
    dev_tps, _ = run(512, temperature=0.8, sampler=True)
    rows.append({
        "metric": "decode_device_sampler_step_delta",
        "value": round((1e3 * slots / host_tps)
                       - (1e3 * slots / dev_tps), 3),
        "unit": "ms/step",
        "note": (f"host-sampler minus device-sampler mean step time at "
                 f"temp 0.8 (host {1e3 * slots / host_tps:.2f} ms, "
                 f"device {1e3 * slots / dev_tps:.2f} ms; device path "
                 f"keeps logits on-device and feeds the donated token "
                 f"buffer back without a host round-trip); 512-token "
                 f"prompts x {slots} slots; {platform}"),
    })
    return rows


def trace_overhead_rows(params, cfg, quick: bool, platform: str = ""):
    """Tracing+metrics overhead on the decode STEP LOOP: the same
    steady full-batch decode measured with the observability layer
    armed (step-timeline ring + SLO metrics + trace spans, the
    defaults) vs stripped. Per-request costs (terminal histograms,
    spans) amortize over a request's tokens; the per-STEP cost is the
    ring recorder's clock reads + deque append, and the acceptance bar
    is <2% on this bench."""
    import statistics as stats

    from ray_tpu.serve.decode import DecodeEngine

    import numpy as np

    slots = 4
    steps = 100 if quick else 200
    repeats = 4 if quick else 6
    capacity = 4096
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).tolist()
               for _ in range(slots)]

    def measure(**obs):
        eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                           prefix_pool_entries=0, **obs)
        # Slots stay occupied for the whole measurement: the loop times
        # pure decode steps, no admissions after warmup.
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
        return stats.median(samples)

    t_off = measure(step_timeline=0, metrics_enabled=False,
                    trace_spans=False)
    t_on = measure()  # config defaults: ring + metrics + spans armed
    overhead = (t_on - t_off) / t_off * 100.0
    return [{
        "metric": "decode_step_overhead_traced_pct",
        "value": round(overhead, 2), "unit": "%",
        "note": (f"decode step loop traced {t_on * 1e6:.0f}us vs "
                 f"untraced {t_off * 1e6:.0f}us per step (median of "
                 f"{repeats} x {steps}-step segments, {slots} active "
                 f"slots; instrumented = step-timeline ring + SLO "
                 f"metrics + trace spans at defaults); bar <2%; "
                 f"{platform}"),
    }]


def serve_stack_row(cfg, quick: bool, platform: str = "",
                    cpu: bool = False):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    import numpy as np

    gen = 8 if quick else 32
    clients = 2 if quick else 8
    duration = 5 if quick else 20
    dep = serve.deployment(LlamaDecodeDeployment).options(
        max_ongoing_requests=64, max_concurrency=32,
        ray_actor_options=(
            {} if quick or cpu else {"resources": {"TPU": 1.0}}),
    ).bind(config=cfg, slots=4 if quick else 16, capacity=256,
           decode_chunk=8)
    serve.run(dep, name="llm_decode")
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if serve.status().get("llm_decode", {}).get("replicas", 0) >= 1:
            break
        time.sleep(0.5)
    handle = serve.get_deployment_handle("llm_decode")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 16 if quick else 64).tolist()
    # Warm (retry through the replica-registration race).
    for _ in range(120):
        try:
            handle.remote({"tokens": prompt, "max_new_tokens": 2}).result(
                timeout=300)
            break
        except RuntimeError:
            time.sleep(1.0)

    stop = time.monotonic() + duration
    lat, tokens = [], [0]
    lock = threading.Lock()

    def client():
        while time.monotonic() < stop:
            t0 = time.monotonic()
            out = handle.remote({"tokens": prompt,
                                 "max_new_tokens": gen}).result(
                timeout=300)
            dt = time.monotonic() - t0
            with lock:
                lat.append(dt * 1e3)
                tokens[0] += len(out["tokens"])

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    rows = [{
        "metric": "decode_serve_stack_tokens_per_s",
        "value": round(tokens[0] / wall, 1),
        "unit": "tokens/s",
        "note": (f"{clients} closed-loop clients x {gen} new tokens/req "
                 f"through controller-routed handle, {len(lat)} reqs, "
                 f"req p50={pctl(lat, 0.5):.0f}ms "
                 f"p99={pctl(lat, 0.99):.0f}ms; nearest-rank pctl; "
                 f"{platform}"),
    }]
    # TTFT/per-token percentiles from the REPLICA-side SLO histograms
    # (serve/metrics.py), aggregated by the cluster controller — the
    # same numbers serve.status()["..."]["slo"] and /metrics report.
    # Replica flushers push every metrics_flush_interval_s; poll.
    from ray_tpu.core.runtime import get_core_worker

    agg = None
    deadline2 = time.monotonic() + 15.0
    while time.monotonic() < deadline2:
        agg = get_core_worker().controller.call("list_metrics",
                                                timeout=10.0)
        if hist_pctl_ms("llm_decode", "serve_ttft_s", 0.5,
                        aggregated=agg) is not None:
            break
        time.sleep(0.5)
    ttft_p50 = hist_pctl_ms("llm_decode", "serve_ttft_s", 0.5,
                            aggregated=agg)
    if ttft_p50 is not None:
        ttft_p99 = hist_pctl_ms("llm_decode", "serve_ttft_s", 0.99,
                                aggregated=agg)
        tok_p50 = hist_pctl_ms("llm_decode", "serve_inter_token_s", 0.5,
                               aggregated=agg)
        tok_p99 = hist_pctl_ms("llm_decode", "serve_inter_token_s",
                               0.99, aggregated=agg)
        rows.append({
            "metric": "decode_serve_stack_ttft_p50",
            "value": round(ttft_p50, 1), "unit": "ms",
            "note": (f"TTFT p99={ttft_p99:.0f}ms, per-token "
                     f"p50={tok_p50:.1f}ms p99={tok_p99:.1f}ms — from "
                     f"the controller-aggregated serve_ttft_s/"
                     f"serve_inter_token_s histograms (bucket-"
                     f"interpolated pctl, same source as serve.status "
                     f"slo + /metrics); {platform}"),
        })
    serve.shutdown()
    return rows


def sharded_rows(quick: bool, platform: str):
    """GSPMD model-parallel decode rows (ISSUE 7): a (2, 4) mesh engine
    vs the single-chip engine on the same model — (a) decode tokens/s
    (on the 1-core CPU host the 8 virtual devices time-slice, so the
    sharded row measures partitioning OVERHEAD, not speedup — a real
    slice gets the model-axis compute in parallel); (b) HBM-per-chip
    headroom: bytes of weights + KV pool resident per device, sharded
    vs single-chip — the model-size unlock, backend-independent.
    Bit-exactness of the sharded logits is asserted in
    tests/test_sharded_decode.py, not re-proven here."""
    import jax
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.decode import DecodeEngine

    if jax.device_count() < 8:
        print("sharded section skipped: needs 8 devices "
              f"(have {jax.device_count()})")
        return []
    # all sharded dims divisible by 8 so the (2, 4) mesh really shards
    cfg = llama.LlamaConfig(
        vocab_size=512, dim=128, n_layers=2 if quick else 4, n_heads=8,
        n_kv_heads=8, mlp_dim=512, max_seq_len=1024)
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    slots, capacity, T = 8, 512, 64
    gen = 16 if quick else 64
    prompts = [rng.integers(0, cfg.vocab_size, 48).tolist()
               for _ in range(slots)]

    def per_chip_bytes(tree):
        """Bytes one device holds of a (possibly sharded) pytree."""
        dev0 = jax.devices()[0]
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                if shard.device == dev0:
                    total += shard.data.nbytes
        return total

    def run(mesh_shape):
        eng = DecodeEngine(params, cfg, slots=slots, capacity=capacity,
                           page_tokens=T, prefix_pool_entries=0,
                           mesh_shape=mesh_shape)
        warm = [eng.submit(p, max_new_tokens=2) for p in prompts[:2]]
        while not all(w.done.is_set() for w in warm):
            eng.step()
        reqs = [eng.submit(p, max_new_tokens=gen) for p in prompts]
        t0 = time.monotonic()
        while not all(r.done.is_set() for r in reqs):
            eng.step()
        wall = time.monotonic() - t0
        toks = sum(len(r.output) for r in reqs)
        return (toks / wall, per_chip_bytes(eng.params),
                per_chip_bytes({"k": eng.cache["k"],
                                "v": eng.cache["v"]}))

    single_tps, single_pb, single_kb = run(None)
    shard_tps, shard_pb, shard_kb = run((2, 4))
    return [
        {"metric": "decode_sharded_tokens_per_s",
         "value": round(shard_tps, 1), "unit": "tok/s",
         "note": (f"(2,4) batch x model mesh over 8 virtual devices vs "
                  f"{single_tps:.1f} tok/s single-chip, same model/"
                  f"capacity (slots={slots}, paged T={T}, +{gen} new); "
                  f"1-core CPU host time-slices the mesh — partitioning "
                  f"overhead row, NOT a speedup claim; logits bit-exact "
                  f"(test_sharded_decode.py); {platform}")},
        {"metric": "decode_sharded_hbm_params_per_chip",
         "value": round(shard_pb / 1e6, 3), "unit": "MB",
         "note": (f"weights resident per chip on the (2,4) mesh vs "
                  f"{single_pb / 1e6:.3f} MB single-chip "
                  f"({single_pb / max(1, shard_pb):.2f}x headroom; "
                  f"wo/w_down stay replicated for bit-exactness); "
                  f"{platform}")},
        {"metric": "decode_sharded_hbm_kv_per_chip",
         "value": round(shard_kb / 1e6, 3), "unit": "MB",
         "note": (f"paged KV pool bytes per chip on the (2,4) mesh vs "
                  f"{single_kb / 1e6:.3f} MB single-chip "
                  f"({single_kb / max(1, shard_kb):.2f}x: kv-head dim "
                  f"shards over the model axis); {platform}")},
    ]


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument(
        "--sections",
        default="engine,serve,shared_prefix,overload,paged,sharded,"
                "spec,trace_overhead",
        help="comma-set of row groups to (re)measure: engine, serve, "
             "shared_prefix, overload, paged, sharded, spec, "
             "trace_overhead. Only the selected groups' rows are "
             "replaced in BENCH_SERVE.json; the rest are preserved.")
    parser.add_argument(
        "--model", default=None,
        help="llama preset override (default: debug if --quick else "
             "160m)")
    parser.add_argument(
        "--cpu", action="store_true",
        help="force JAX_PLATFORMS=cpu but still write BENCH_SERVE.json "
             "(rows are annotated with the platform)")
    args = parser.parse_args()
    sections = {s.strip() for s in args.sections.split(",") if s.strip()}
    if "serve" in sections and not (args.quick or args.cpu):
        # A chip belongs to one process: this driver builds params and
        # runs the engine rows on the chip itself, so the TPU: 1 replica
        # of the serve rows could never open it.
        parser.error(
            "the 'serve' rows start a TPU: 1 replica, but this driver "
            "holds the chip (it builds params and runs the engine rows "
            "in-process); pass --cpu or drop 'serve' from --sections")

    if "sharded" in sections:
        # The sharded rows span an 8-device mesh; on a CPU host that
        # means the forced virtual devices (must be set before jax
        # initializes its backend).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    if args.quick or args.cpu:
        # Env var too: serve replica workers inherit it at fork, so the
        # whole quick path (driver + replicas) stays on CPU.
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    import ray_tpu
    from ray_tpu.models import llama

    preset = args.model or ("debug" if args.quick else "160m")
    cfg = llama.PRESETS[preset]
    params = llama.init_params(cfg, jax.random.key(0))
    platform = jax.devices()[0].platform
    plat_note = f"{preset} model, {platform} backend"

    rows = []
    if "engine" in sections:
        rows += engine_rows(params, cfg, args.quick, plat_note)
    if "shared_prefix" in sections:
        rows += shared_prefix_rows(params, cfg, args.quick, plat_note)
    if "overload" in sections:
        rows += overload_rows(params, cfg, args.quick, plat_note)
    if "paged" in sections:
        rows += paged_rows(args.quick, f"{platform} backend")
    if "sharded" in sections:
        rows += sharded_rows(args.quick, f"{platform} backend")
    if "spec" in sections:
        rows += spec_rows(args.quick, f"{platform} backend")
    if "trace_overhead" in sections:
        rows += trace_overhead_rows(params, cfg, args.quick, plat_note)
    if "serve" in sections:
        ray_tpu.init(num_cpus=4)
        try:
            rows += serve_stack_row(cfg, args.quick, plat_note,
                                    cpu=args.cpu)
        finally:
            ray_tpu.shutdown()

    out_path = "BENCH_SERVE.json"
    doc = {"artifact": "BENCH_SERVE", "rows": []}
    if os.path.exists(out_path) and not args.quick:
        with open(out_path) as f:
            doc = json.load(f)
        # Replace exactly the rows this run re-measured.
        emitted = {r["metric"] for r in rows}
        doc["rows"] = [r for r in doc.get("rows", [])
                       if r["metric"] not in emitted]
    if args.quick:
        out_path = "/tmp/bench_decode_quick.json"
    doc.setdefault("decode_model",
                   "llama-160m, KV-cache continuous batching "
                   "(serve/decode.py), bf16")
    doc["rows"] = doc.get("rows", []) + rows
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
