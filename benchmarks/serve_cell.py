"""A serve cell: one replica of the family's deployment class under the
benchmark's hooks (``serve_replica.py``) on one chip behind the HTTP proxy,
loaded by ``client.py`` from this process. What belongs to the served
architecture (model config, deployment class, reference, tolerance, the
check's sizes) comes from the ``Serve`` of the configuration's family."""

from __future__ import annotations

import asyncio
import random
import statistics
import time
from typing import Dict, List

from benchmarks import client, stats, traffic

READY_TIMEOUT_S = 900
TRACE_SECONDS = 5.0


def _wait_replica(serve, name: str) -> Dict:
    deadline = time.monotonic() + READY_TIMEOUT_S
    while time.monotonic() < deadline:
        st = serve.status().get(name, {})
        topo = [r for r in st.get("replica_topology", []) if r.get("device")]
        if st.get("replicas") == 1 and len(topo) == 1:
            return topo[0]
        time.sleep(0.5)
    raise TimeoutError(f"replica of {name} not up in {READY_TIMEOUT_S}s")


def check_requests(sizes: Dict, seed: int) -> List[traffic.Request]:
    """The check's prompts: ``sizes`` as ``families.SERVE_CHECK`` has them,
    lengths and token ids from the seed."""
    rng = random.Random(seed)
    low, high = sizes["prompt_len"]
    return [traffic.Request(i, "check", 0.0, rng.randrange(low, high + 1),
                            sizes["tokens"], rng.getrandbits(48))
            for i in range(sizes["prompts"])]


def _check(addr, route, fam, handle, seed) -> Dict:
    """A few seeded prompts through the HTTP path, then the family's
    reference."""
    vocab = fam.vocab
    reqs = check_requests(fam.check, seed)

    async def go():
        sink: List[client.Outcome] = []
        await asyncio.gather(*[
            client.send(addr, route, vocab,
                        client.Outcome(r, f"check-{r.index}"), sink)
            for r in reqs])
        return sink

    outs = sorted(asyncio.run(go()), key=lambda o: o.request.index)
    bad = [o.error or "short answer" for o in outs if not o.ok]
    margins = [float("inf")]
    if not bad:
        margins = handle.bench_reference_margins.remote(
            [r.tokens(vocab) for r in reqs],
            [o.tokens for o in outs]).result(timeout=600)
    worst = max(margins)
    return {"attempted": len(reqs), "failed": len(bad), "errors": bad,
            "ok": not bad and worst <= fam.tolerance,
            "said": f"served-token margin {worst:.4f} <= {fam.tolerance} "
                    f"({fam.reference})"}


def _print_halves(measured, dump, marks, seconds) -> None:
    """First against second half of the window: a rate above the knee
    shows as waits and occupancy that grow through the run (KNEE.md)."""
    mid = marks["open"]["monotonic"] + seconds / 2
    wall_mid = marks["open_wall"] + seconds / 2
    for label, pick in (("first half", lambda t: t < mid),
                        ("second half", lambda t: t >= mid)):
        outs = [o for o in measured if o.ok and pick(o.due)]
        waits = [(c[1] - c[0]) * 1e3 for o in outs
                 for c in [dump["clocks"].get(o.request_id)]
                 if c and c[1] is not None]
        rows = [r for r in dump["rows"]
                if marks["open_wall"] <= r["t0"] < marks["open_wall"] + seconds
                and (r["t0"] < wall_mid) == (label == "first half")]
        ttft = [stats.ttft_ms(o.due, o.arrivals[0]) for o in outs]
        print(f"[bench] {label}: {len(outs)} requests, ttft median "
              f"{statistics.median(ttft) if ttft else float('nan'):.1f} ms, "
              f"engine queue wait mean "
              f"{statistics.fmean(waits) if waits else float('nan'):.1f} ms"
              + (f", active slots mean "
                 f"{statistics.fmean(r['active'] for r in rows):.2f}, queued "
                 f"mean {statistics.fmean(r['queued'] for r in rows):.2f}"
                 if rows else ""), flush=True)


def run(cell: Dict, args, t_proc_wall: float, work_dir: str) -> Dict:
    from ray_tpu import serve

    from benchmarks.serve_replica import bench_deployment

    cfg, mix, fam = cell["config"], cell["traffic"], cell["serve"]
    layout = dict(cfg["serve"]["layouts"][mix.get("layout", "default")])
    max_ongoing = layout.pop("max_ongoing_requests")
    vocab = fam.vocab
    dep = serve.deployment(bench_deployment(cfg["family"])).options(
        max_ongoing_requests=max_ongoing,
        ray_actor_options={"resources": {"TPU": 1}}).bind(
            config=fam.model_cfg, seed=args.seed % (2 ** 31 - 1), **layout)
    serve.run(dep, name="llm", ready_timeout_s=READY_TIMEOUT_S)
    addr = serve.start_http()
    _wait_replica(serve, "llm")
    handle = serve.get_deployment_handle("llm")
    route = "/llm"

    def call(method: str, *a, timeout: float = 120.0):
        return getattr(handle, method).remote(*a).result(timeout=timeout)

    # ---- set-up: every prefill shape of this cell's traffic, once -------
    open_loop = mix["loop"] == "open"
    if open_loop:
        planned = schedule = traffic.open_loop_schedule(mix, args.seed,
                                                        args.seconds)
    else:
        planned = requests = traffic.closed_loop_requests(mix, args.seed)
    lengths = [r.prompt_len for r in planned]
    chunk = layout["prefill_chunk_tokens"]
    page = layout["kv_page_tokens"]
    singles = traffic.warm_lengths(lengths, chunk, page)
    groups = [[n] for n in singles]
    for wave in mix.get("warm_waves", []):
        groups += [[n] * wave for n in singles if n <= chunk]
    if mix.get("warm_resumed"):
        have = set().union(*(traffic.prefill_programs(n, chunk, page)
                             for n in singles))
        longest = max(r.prompt_len + r.answer_len for r in planned)
        call("bench_warm_resumed", traffic.resumed_prefills(
            longest, chunk, page, have), vocab, timeout=1500.0)
    call("bench_warm", groups, vocab, timeout=1500.0)

    # ---- lead-in, window, drain ---------------------------------------
    seconds = float(args.seconds)
    lead = float(mix["lead_in_s"])
    t_start = time.monotonic() + 0.25
    t_open, t_close = t_start + lead, t_start + lead + seconds
    hard_stop = t_close + float(mix["drain_s"])
    marks: Dict[str, Dict] = {}
    traced = bool(args.trace)
    trace_dir = f"{work_dir}/trace"

    async def edges():
        """Window marks (and, traced, the profiler) on the replica."""
        loop = asyncio.get_running_loop()

        def at(name, *a):
            return loop.run_in_executor(None, lambda: call(name, *a))

        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        if traced:
            await at("bench_keep_steps")
        marks["open"] = await at("bench_mark")
        marks["open_wall"] = time.time()
        if traced:
            await asyncio.sleep(max(
                0.0, t_close - TRACE_SECONDS - 1.0 - time.monotonic()))
            await at("bench_trace", True, trace_dir)
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        marks["close"] = await at("bench_mark")
        if traced:
            await at("bench_trace", False)

    sink: List[client.Outcome] = []
    n_window = (sum(1 for r in schedule if r.phase == "window")
                if open_loop else 0)

    def measured_ended() -> bool:
        win = [o for o in sink if o.request.phase == "window"]
        return (time.monotonic() > t_close and len(win) == n_window
                and all(o.ended or o.error for o in win))

    async def load():
        if open_loop:
            sending = client.open_loop(
                addr, route, vocab, schedule, t_start, measured_ended,
                hard_stop, f"s{args.seed}", sink)
        else:
            sending = client.closed_loop(
                addr, route, vocab, requests, int(mix["clients"]), t_close,
                f"s{args.seed}", sink)
        await asyncio.gather(sending, edges())

    asyncio.run(load())
    outcomes = sink
    dump = call("bench_dump")
    check = _check(addr, route, fam, handle, args.seed)

    # ---- end-to-end numbers, from the client's clock alone ----------------
    credits = []
    for o in outcomes:
        if o.arrivals:
            credits.append((o.arrivals[0], o.request.prompt_len))
            credits += [(t, 1) for t in o.arrivals]
    e2e = {"serve_tokens_per_s": stats.tokens_per_s(credits, t_open, t_close)}
    if open_loop:
        measured = [o for o in outcomes if o.request.phase == "window"]
        never_sent = n_window - len(measured)
        ttft, tpot, no_gap = stats.request_latencies(measured, never_sent)
        failed = sum(1 for o in measured if not o.ok) + never_sent
        e2e["ttft_p90_ms"] = stats.percentile(ttft, 90)
        e2e["tpot_p90_ms"] = stats.percentile(tpot, 90)
        print(f"[bench] {len(ttft)} measured requests, {failed} failed, "
              f"{no_gap} left out of tpot (all tokens in the first "
              f"delivery); ttft median {statistics.median(ttft):.4f} ms, "
              f"tpot median {statistics.median(tpot):.4f} ms over "
              f"{len(tpot)}; {check['said']}", flush=True)
        attempted = n_window
        for o in measured:
            if not o.ok:
                print(f"[bench] failed: {o.request_id} prompt "
                      f"{o.request.prompt_len} answer {len(o.arrivals)}/"
                      f"{o.request.answer_len}: {o.error}", flush=True)
        _print_halves(measured, dump, marks, seconds)
    else:
        inside = [o for o in outcomes if t_open <= o.due < t_close]
        # Requests cut off by the end of the run are not failures.
        failed = sum(1 for o in inside if o.error
                     and "cancelled" not in o.error)
        done_in = [o for o in outcomes if o.ok and t_open <= o.ended < t_close]
        print(f"[bench] {len(done_in)} requests ended inside the window, "
              f"median request "
              f"{statistics.median([o.ended - o.sent for o in done_in]):.4f}"
              f" s, {failed} failed; {check['said']}", flush=True)
        attempted = len(inside)
    e2e["setup_s"] = marks["open_wall"] - t_proc_wall
    compiles = marks["close"]["compiles"] - marks["open"]["compiles"]
    print(f"[bench] inside the window: {compiles} compiles, "
          f"{marks['close']['preempted'] - marks['open']['preempted']} "
          f"preemptions, {marks['close']['steps'] - marks['open']['steps']} "
          f"engine steps; at its close {marks['close']['active']} active, "
          f"{marks['close']['queued']} queued", flush=True)
    events = [e for r in dump["rows"] for e in r.get("events", [])
              if marks["open_wall"] <= e["ts"] < marks["open_wall"] + seconds
              and e["kind"] in ("jit-compile", "preempt")]
    if events:
        print(f"[bench] step-log events inside the window: "
              f"{[{k: v for k, v in e.items() if k != 'ts'} for e in events]}",
              flush=True)
    mark = marks["close"]
    peak = max(x or 0 for x in mark["peak_bytes_in_use"])
    return {
        "kind": "serve", "correct": check["ok"] and failed == 0,
        "attempted": attempted + check["attempted"],
        "failed": failed + check["failed"],
        "end_to_end": e2e, "outcomes": outcomes, "clocks": dump["clocks"],
        "rows": dump["rows"], "marks": marks,
        "window": (t_open, t_close),
        "compiles_in_window": compiles,
        "trace_dir": trace_dir if traced else None,
        "device": {"platform": mark["platform"], "kind": mark["device_kind"],
                   "count": mark["device_count"],
                   "memory_peak_bytes": peak}}
