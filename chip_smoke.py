#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives both payloads once through the entry points a user calls, on however
many chips this machine has, and checks what comes out:

* serve: ``ray_tpu.init()`` -> ``serve.run(serve.deployment(
  LlamaDecodeDeployment)...bind(preset="1b", paged, chunked prefill))`` ->
  ``serve.start_http()`` -> HTTP POSTs (plain, repeated, streamed long
  prompt, eight at once) -> ``serve.shutdown()``;
* train: ``JaxTrainer(loop, ScalingConfig(use_tpu=True, ...)).fit()`` with
  ``build_train_step`` on ``MeshSpec(fsdp=-1)``, three optimizer steps on
  one seeded batch, Pallas flash attention checked against the XLA
  reference inside the worker;
* on a four-chip host also: a ``(1, 4)`` mesh replica, two ``TPU: 1``
  replicas behind one router, and the four-way FSDP trainer.

The chip goes to the WORKERS: this driver never initializes a JAX backend
(asserted at the end). Exit 0 and a last line of
``{"ok": true, "device": {...}}`` only if every check of every phase passed;
no chip, ``JAX_PLATFORMS=cpu``, a failed check, a raised phase or the
deadline is a non-zero exit and no result line.

``--dry-run-cpu[=N]`` (never a default, never implied) runs the same control
flow at the ``debug`` preset on N virtual CPU devices and labels its output
a dry run — for debugging this script without spending chip time.
"""

from __future__ import annotations

import http.client
import json
import math
import os
import random
import signal
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NoReturn

DEADLINE_S = 1100          # whole run, compilation included (limit: 1200)
READY_TIMEOUT_S = 420      # replica start: process, params, engine
REQUEST_TIMEOUT_S = 300    # one request, cold compiles included
T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def fail(msg: str) -> NoReturn:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class Checks:
    """Every check is recorded and printed; one failure fails the run."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"  {'ok  ' if ok else 'FAIL'} {name}" +
            (f" — {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok


# ------------------------------------------------------------------ HTTP


def post(addr, route: str, payload: dict, stream: bool = False) -> dict:
    """One request to the serve proxy. Streamed responses are read chunk
    by chunk off the raw socket so HTTP chunks can be counted and timed."""
    conn = http.client.HTTPConnection(addr[0], addr[1],
                                      timeout=REQUEST_TIMEOUT_S + 30)
    headers = {"Content-Type": "application/json",
               "X-Request-Timeout-S": str(REQUEST_TIMEOUT_S)}
    if stream:
        headers["X-Serve-Stream"] = "1"
        payload = dict(payload, stream=True)
    t0 = time.monotonic()
    try:
        conn.request("POST", route, body=json.dumps(payload),
                     headers=headers)
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status} from {route}: "
                               f"{resp.read()[:500]!r}")
        if not stream:
            out = json.loads(resp.read())
            out["wall_s"] = time.monotonic() - t0
            return out
        if not resp.chunked:
            raise RuntimeError("streamed response is not chunked")
        tokens, chunk_times = [], []
        while True:
            size = int(resp.fp.readline().split(b";")[0].strip() or b"0",
                       16)
            if size == 0:
                break
            data = resp.fp.read(size)
            resp.fp.read(2)  # CRLF
            chunk_times.append(time.monotonic() - t0)
            for line in data.splitlines():
                item = json.loads(line)
                if isinstance(item, dict):
                    raise RuntimeError(f"stream error record: {item}")
                tokens.append(item)
        return {"tokens": tokens, "chunks": len(chunk_times),
                "ttft_s": chunk_times[0] if chunk_times else None,
                "wall_s": time.monotonic() - t0}
    finally:
        conn.close()


def valid_tokens(out: dict, n: int, vocab: int) -> bool:
    toks = out.get("tokens")
    return (isinstance(toks, list) and len(toks) == n
            and all(isinstance(t, int) and 0 <= t < vocab for t in toks))


def post_many(addr, route: str, payloads: list) -> list:
    """All of ``payloads`` at once; any failed request raises."""
    with ThreadPoolExecutor(len(payloads)) as pool:
        return list(pool.map(lambda p: post(addr, route, p), payloads))


def prompt(seed: int, n: int, vocab: int) -> list:
    rng = random.Random(seed)
    return [rng.randrange(vocab) for _ in range(n)]


# ----------------------------------------------------------------- serve


def wait_replicas(serve, name: str, n: int) -> list:
    """Block until ``n`` replicas of ``name`` have reported where they run
    (their first stats reply carries the device record)."""
    deadline = time.monotonic() + READY_TIMEOUT_S
    topo = []
    while time.monotonic() < deadline:
        st = serve.status().get(name, {})
        topo = [r for r in st.get("replica_topology", []) if r.get("device")]
        if st.get("replicas") == n and len(topo) == n:
            return topo
        time.sleep(1.0)
    raise TimeoutError(f"{name}: {len(topo)}/{n} replicas reported a device "
                       f"within {READY_TIMEOUT_S}s: {serve.status()}")


def balanced(values, tol: float = 0.25) -> bool:
    vals = [v for v in values if v is not None]
    return (len(vals) == len(values) and min(vals) > 0
            and max(vals) <= (1 + tol) * min(vals))


def serve_phase(check: Checks, serve, n: int, model: dict, dev_expect: dict,
                report: dict, dry: bool) -> None:
    """One replica holding all ``n`` chips (mesh (1, n) when n > 1): the
    four request shapes, then the zero-recompile repeat."""
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    t_phase = time.monotonic()
    vocab, new = model["vocab"], model["max_new_tokens"]
    kwargs = dict(preset=model["preset"], slots=8,
                  capacity=model["capacity"],
                  kv_page_tokens=model["page_tokens"],
                  prefill_chunk_tokens=model["chunk_tokens"])
    if n > 1:
        kwargs["mesh_shape"] = (1, n)
    dep = serve.deployment(LlamaDecodeDeployment).options(
        max_ongoing_requests=32,
        ray_actor_options={"resources": {"TPU": n}}).bind(**kwargs)
    serve.run(dep, name="llm", ready_timeout_s=READY_TIMEOUT_S)
    addr = serve.start_http()
    (rep,) = wait_replicas(serve, "llm", 1)
    dev = rep["device"]
    log(f"serve: replica up in {time.monotonic() - t_phase:.1f}s on "
        f"{dev['platform']} {dev['device_kind']!r} devices "
        f"{dev['device_ids']} (pid {dev['pid']})")
    check("serve: replica platform", dev["platform"] == dev_expect["platform"],
          f"{dev['platform']} {dev['device_kind']!r}")
    check("serve: replica spans the lease's chips",
          len(dev["device_ids"]) == n and dev["device_count"] == n,
          f"device_ids={dev['device_ids']} device_count={dev['device_count']}")

    handle = serve.get_deployment_handle("llm")

    def health() -> dict:
        return handle.health.remote().result(timeout=60)

    short = prompt(1, model["short_prompt"], vocab)
    a = post(addr, "/llm", {"tokens": short, "max_new_tokens": new})
    check("serve (a): short prompt, plain",
          valid_tokens(a, new, vocab) and a.get("ttft_s") is not None,
          f"ttft_s={a.get('ttft_s')} wall={a['wall_s']:.1f}s")
    b = post(addr, "/llm", {"tokens": short, "max_new_tokens": new})
    h = health()
    check("serve (b): same prompt again hits the paged prefix index",
          valid_tokens(b, new, vocab) and b.get("ttft_s") is not None
          and h.get("prefix", {}).get("hits", 0) >= 1
          and h.get("pages_pinned", 0) > 0,
          f"prefix={h.get('prefix')} pages_pinned={h.get('pages_pinned')}")
    chunks0 = h["prefill_chunks"]
    c = post(addr, "/llm", {"tokens": prompt(2, model["long_prompt"], vocab),
                            "max_new_tokens": new}, stream=True)
    h = health()
    want_chunks = -(-model["long_prompt"] // model["chunk_tokens"])
    check("serve (c): long prompt, streamed over chunked HTTP",
          valid_tokens(c, new, vocab) and c["chunks"] > 1
          and h["prefill_chunks"] - chunks0 >= want_chunks,
          f"{c['chunks']} HTTP chunks, first after {c['ttft_s']:.2f}s, "
          f"{h['prefill_chunks'] - chunks0} prefill chunks "
          f"(>= {want_chunks})")
    outs = post_many(addr, "/llm", [
        {"tokens": prompt(10 + i, model["short_prompt"], vocab),
         "max_new_tokens": new} for i in range(8)])
    rows = serve.timelines()["llm"][rep["replica_id"]]["rows"]
    peak_active = max((r.get("active", 0) for r in rows), default=0)
    check("serve (d): eight at once share decode steps",
          all(valid_tokens(o, new, vocab) and o.get("ttft_s") is not None
              for o in outs)
          and peak_active > 1, f"peak active slots in one step: "
                               f"{peak_active}")
    before = health()["device"]
    again = post(addr, "/llm", {"tokens": short, "max_new_tokens": new})
    after = health()["device"]
    check("serve: repeat of (a) compiles nothing",
          valid_tokens(again, new, vocab)
          and after["compiles"] == before["compiles"],
          f"compiles {before['compiles']} -> {after['compiles']}")

    total = 12
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = serve.status()["llm"]
        done = st.get("slo", {}).get("outcomes", {}).get("completed", 0)
        if done >= total:
            break
        time.sleep(1.0)
    check("serve: status after traffic",
          st["replicas"] == 1 and done == total and st["shed"] == 0
          and st["cancelled"] == 0 and st["deadline_exceeded"] == 0,
          f"replicas={st['replicas']} completed={done}/{total} "
          f"shed={st['shed']} cancelled={st['cancelled']} "
          f"deadline_exceeded={st['deadline_exceeded']}")
    if n > 1 and not dry:  # the CPU backend reports no memory stats
        check("serve: per-device memory balanced (within 25%)",
              balanced(after["bytes_in_use"]),
              f"bytes_in_use={after['bytes_in_use']}")
    report["serve"] = {
        "platform": after["platform"], "device_kind": after["device_kind"],
        "devices": n, "wall_s": round(time.monotonic() - t_phase, 1),
        "compiles": after["compiles"], "compile_s": after["compile_s"],
        "cache_hits": after["cache_hits"],
        "bytes_in_use": after["bytes_in_use"],
        "peak_bytes_in_use": after["peak_bytes_in_use"]}
    log(f"serve: {json.dumps(report['serve'])}")
    serve.delete("llm")


def two_replica_phase(check: Checks, serve, model: dict, dev_expect: dict,
                      report: dict) -> None:
    """Two TPU: 1 replicas of one deployment behind one router: each lease
    must get its own chip, and both must answer."""
    from ray_tpu.serve.decode import LlamaDecodeDeployment

    t_phase = time.monotonic()
    dep = serve.deployment(LlamaDecodeDeployment).options(
        num_replicas=2, max_ongoing_requests=4,
        ray_actor_options={"resources": {"TPU": 1}}).bind(
            preset=model["small_preset"], slots=4, capacity=256,
            kv_page_tokens=16, prefill_chunk_tokens=64)
    serve.run(dep, name="pair", ready_timeout_s=READY_TIMEOUT_S)
    addr = serve.start_http()
    topo = wait_replicas(serve, "pair", 2)
    devs = [r["device"] for r in topo]
    check("pair: both replicas on the accelerator, one device each",
          all(d["platform"] == dev_expect["platform"]
              and len(d["device_ids"]) == 1 for d in devs),
          f"{[(d['platform'], d['device_ids']) for d in devs]}")
    check("pair: each lease got its own chip",
          len({d["visible_chips"] for d in devs}) == 2
          and len({d["pid"] for d in devs}) == 2,
          f"visible_chips={[d['visible_chips'] for d in devs]} "
          f"pids={[d['pid'] for d in devs]}")
    small_vocab = model["small_vocab"]
    outs = []
    for _round in range(3):
        outs += post_many(addr, "/pair", [
            {"tokens": prompt(30 + i, 24, small_vocab),
             "max_new_tokens": 24} for i in range(8)])
    handle = serve.get_deployment_handle("pair")
    seen = {}
    for _ in range(64):
        h = handle.health.remote().result(timeout=60)
        seen[h["device"]["pid"]] = h["tokens_out"]
        if len(seen) == 2:
            break
    check("pair: both replicas answered through one router",
          len(outs) == 24 and all(valid_tokens(o, 24, small_vocab)
                                  for o in outs)
          and len(seen) == 2 and all(v > 0 for v in seen.values()),
          f"tokens_out by replica pid: {seen}")
    report["pair"] = {"wall_s": round(time.monotonic() - t_phase, 1),
                      "visible_chips": [d["visible_chips"] for d in devs]}
    log(f"pair: {json.dumps(report['pair'])}")
    serve.delete("pair")


# ----------------------------------------------------------------- train


def train_loop(config: dict) -> None:
    """Runs inside the TrainWorker — the process that holds the chips."""
    import dataclasses
    import math
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.ops.attention import attention
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.util.compile_cache import compile_watch

    watch = compile_watch()
    t0 = time.monotonic()
    devices = jax.devices()
    n = len(devices)
    cfg = llama.LlamaConfig(**config["model"]) if "model" in config \
        else llama.PRESETS[config["preset"]]
    cfg = dataclasses.replace(cfg, **config["flags"])
    seq, micro, accum = config["seq"], config["microbatch"], config["accum"]

    mesh = MeshSpec(fsdp=-1).build()
    params = ts.init_sharded_params(
        lambda key: llama.init_params(cfg, key), llama.param_axes(cfg),
        mesh, jax.random.key(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = ts.init_optimizer_state(opt, params)
    step_fn = ts.build_train_step(lambda p, b: llama.loss_fn(p, b, cfg),
                                  opt, mesh, accum_steps=accum)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (micro * accum, seq + 1)).astype(np.int32)
    batch = ts.shard_batch({"tokens": tokens}, mesh)
    # One AOT compile serves the HLO inspection and the steps.
    compiled = step_fn.lower(params, opt_state, batch).compile()
    hlo = compiled.as_text()
    from __graft_entry__ import _collective_counts

    # Result shapes of the Pallas calls as compiled: under GSPMD they show
    # whether the kernel runs on a shard of the batch or on all of it.
    kernel_shapes = sorted({
        line.split("=", 1)[1].split("custom-call(", 1)[0].strip()[:120]
        for line in hlo.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line})[:6]

    losses = []
    for step in range(3):
        params, opt_state, metrics = compiled(params, opt_state, batch)
        losses.append(float(metrics["loss"]))  # host fetch: step is done
        train.report({"step": step, "loss": losses[-1]})
    # Memory is read here, before the attention check below puts its own
    # (single-device, full-score-matrix) buffers on device 0.
    mem = [d.memory_stats() or {} for d in devices]
    del params, opt_state, compiled, batch

    # Pallas flash attention against the XLA reference, forward and grad,
    # on one seeded bf16 input of the step's own shape. Tolerance: both
    # paths accumulate in fp32 but block and sum in different orders and
    # round their outputs to bf16, so they differ by about one bf16 ulp
    # at the magnitude of the largest value compared (measured on a v5e:
    # forward 0.002-0.004 at |x| <= 3.6, grads 0.016-0.031 at |x| <= 7);
    # two ulps are allowed.
    def ulp2(ref) -> float:
        m = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        return 2.0 * 2.0 ** (math.floor(math.log2(max(m, 1e-6))) - 7)

    ks = jax.random.split(jax.random.key(7), 4)
    shape_q = (micro, seq, cfg.n_heads, cfg.head_dim)
    shape_kv = (micro, seq, cfg.n_kv_heads, cfg.head_dim)
    q = jax.random.normal(ks[0], shape_q, jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], shape_kv, jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], shape_kv, jnp.float32).astype(jnp.bfloat16)
    g = jax.random.normal(ks[3], shape_q, jnp.float32).astype(jnp.bfloat16)

    def scalar(f):
        return lambda q, k, v: jnp.sum((f(q, k, v) * g).astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def ref(q, k, v):
        return attention(q, k, v, causal=True)

    errs = {}
    o_f, o_r = jax.jit(flash)(q, k, v), jax.jit(ref)(q, k, v)
    errs["fwd"] = (float(jnp.max(jnp.abs(o_f.astype(jnp.float32)
                                         - o_r.astype(jnp.float32)))),
                   ulp2(o_r))
    g_f = jax.jit(jax.grad(scalar(flash), argnums=(0, 1, 2)))(q, k, v)
    g_r = jax.jit(jax.grad(scalar(ref), argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_f, g_r):
        errs[name] = (float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b.astype(jnp.float32)))),
                      ulp2(b))
    train.report({
        "final": True, "losses": losses,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind, "devices": n,
        "mesh": dict(mesh.shape), "params_m": round(cfg.num_params() / 1e6),
        "interpret": jax.default_backend() != "tpu",
        "tpu_custom_call": "tpu_custom_call" in hlo,
        "kernel_shapes": kernel_shapes,
        "collectives": _collective_counts(hlo),
        "flash_vs_reference": errs,
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "wall_s": round(time.monotonic() - t0, 1),
        **watch.snapshot()})


def train_phase(check: Checks, n: int, model: dict, dev_expect: dict,
                report: dict, dry: bool) -> None:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    t_phase = time.monotonic()
    result = JaxTrainer(
        train_loop, train_loop_config=model["train"],
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpu_chips_per_worker=n)).fit()
    if result.error:
        raise RuntimeError(f"train loop failed: {result.error}")
    final = result.metrics
    if not final or not final.get("final"):
        raise RuntimeError(f"train loop reported no final record: {final}")
    losses = final["losses"]
    ln_v = math.log(model["train_vocab"])
    check("train: platform", final["platform"] == dev_expect["platform"]
          and final["devices"] == n,
          f"{final['platform']} {final['device_kind']!r} x{final['devices']} "
          f"mesh={final['mesh']} params={final['params_m']}M")
    # lm_head is initialised with std 1/sqrt(dim) over a unit-RMS hidden
    # state, so initial logits are ~N(0, 1) and the expected first loss is
    # ln V + 1/2 (measured 10.79-10.93 at V=32000), not ln V.
    check("train: loss finite, starts at ~ln V + 1/2, falls",
          all(math.isfinite(x) for x in losses)
          and ln_v <= losses[0] <= ln_v + 0.75
          and losses[-1] < losses[0],
          f"losses={[round(x, 4) for x in losses]} ln V={ln_v:.3f}")
    if dry:
        check("train: (dry run) flash runs in interpret mode",
              final["interpret"] and not final["tpu_custom_call"])
    else:
        check("train: Pallas kernel is in the step's HLO",
              final["tpu_custom_call"] and not final["interpret"],
              f"kernel result shapes: {final['kernel_shapes']}")
    check("train: flash agrees with the XLA reference (fwd, dq, dk, dv)",
          all(err <= tol for err, tol in final["flash_vs_reference"].values()),
          f"(max abs err, 2-ulp tol): {final['flash_vs_reference']}")
    if n > 1:
        coll = final["collectives"]
        check("train: FSDP collectives in the step",
              coll.get("all-gather", 0) > 0
              and (coll.get("reduce-scatter", 0) > 0
                   or coll.get("all-reduce", 0) > 0), f"{coll}")
    if n > 1 and not dry:
        check("train: per-device memory balanced (within 25%)",
              balanced(final["bytes_in_use"]),
              f"bytes_in_use={final['bytes_in_use']}")
    report["train"] = {
        "platform": final["platform"], "device_kind": final["device_kind"],
        "devices": final["devices"],
        "wall_s": round(time.monotonic() - t_phase, 1),
        "worker_wall_s": final["wall_s"], "compiles": final["compiles"],
        "compile_s": final["compile_s"], "cache_hits": final["cache_hits"],
        "bytes_in_use": final["bytes_in_use"],
        "peak_bytes_in_use": final["peak_bytes_in_use"]}
    log(f"train: {json.dumps(report['train'])}")


# ------------------------------------------------------------------ main


def models(n: int, dry: bool) -> dict:
    """What each phase runs: full-width configurations on the chip, the
    debug preset in a dry run (same control flow, toy shapes)."""
    flags = dict(attention_impl="flash", fused_qkv=True, fused_mlp=True,
                 embed_via_matmul=True, loss_chunk=1024, embed_chunk=1024)
    if dry:
        return {"preset": "debug", "vocab": 256, "capacity": 128,
                "page_tokens": 8, "chunk_tokens": 16, "short_prompt": 20,
                "long_prompt": 50, "max_new_tokens": 6,
                "small_preset": "debug", "small_vocab": 256,
                "train_vocab": 256,
                "train": {"preset": "debug", "seq": 64,
                          "microbatch": max(n, 2), "accum": 2,
                          "flags": dict(flags, loss_chunk=32,
                                        embed_chunk=64)}}
    out = {"preset": "1b", "vocab": 32000, "capacity": 2048,
           "page_tokens": 64, "chunk_tokens": 512, "short_prompt": 150,
           "long_prompt": 1500, "max_new_tokens": 16,
           "small_preset": "debug", "small_vocab": 256,
           "train_vocab": 32000}
    if n == 1:
        # The configuration the pre-ledger chip rows ran (bench.py d1280).
        out["train"] = {
            "model": dict(vocab_size=32000, dim=1280, n_layers=24,
                          n_heads=10, n_kv_heads=10, mlp_dim=5120,
                          max_seq_len=2048),
            "seq": 2048, "microbatch": 3, "accum": 4, "flags": flags}
    else:
        out["train"] = {"preset": "1b", "seq": 2048, "microbatch": n,
                        "accum": 4, "flags": flags}
    return out


def leftover_processes(node_hex: str) -> list:
    """Worker/forkserver processes of OUR node still alive."""
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
        if node_hex in cmd and state != "Z" and (
                "worker_main" in cmd or "forkserver" in cmd):
            out.append((int(pid), cmd[:120]))
    return out


def main() -> None:
    dry_arg = next((a for a in sys.argv[1:]
                    if a.startswith("--dry-run-cpu")), None)
    if [a for a in sys.argv[1:] if a != dry_arg]:
        fail(f"unknown arguments {sys.argv[1:]}; usage: chip_smoke.py "
             f"[--dry-run-cpu[=N]]")
    dry = dry_arg is not None
    if dry:
        n_dry = int(dry_arg.partition("=")[2] or 1)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_dry}")
        log(f"DRY RUN on {n_dry} virtual CPU device(s): control flow only, "
            f"debug preset — this proves nothing about the chip")
    else:
        platforms = os.environ.get("JAX_PLATFORMS", "").lower()
        if platforms and "tpu" not in platforms.split(","):
            fail(f"JAX_PLATFORMS={platforms!r} keeps JAX off the "
                 f"accelerator; this check needs the chip")
    try:
        import ray_tpu
        from ray_tpu import serve, tpu
    except ImportError as e:
        fail(f"cannot import ray_tpu ({e}); run from the repo checkout")
    if not dry and not tpu.accelerator_device_files():
        fail("no accelerator: this machine has no TPU device files "
             "(/dev/accel*, /dev/vfio/<n>)")

    def on_deadline(_signum, _frame):
        raise TimeoutError(f"chip_smoke exceeded its {DEADLINE_S}s deadline")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    check = Checks()
    report: dict = {}
    # init() probes the chips in a subprocess that exits before any worker
    # starts; a broken or busy runtime raises TpuProbeError out of here.
    core = ray_tpu.init(num_cpus=8, **(
        {"resources": {"TPU": n_dry}} if dry else {}))
    node_hex = core.node_id.hex()
    try:
        n = int(ray_tpu.cluster_resources().get("TPU", 0))
        if n < 1:
            fail("no accelerator: the chip probe found 0 TPU chips")
        dev_expect = {"platform": "cpu" if dry else "tpu"}
        log(f"cluster up: TPU={n} (from the node's detected resources)")
        model = models(n, dry)
        try:
            serve_phase(check, serve, n, model, dev_expect, report, dry)
            if n > 1:
                two_replica_phase(check, serve, model, dev_expect, report)
        finally:
            # The replica's process must be gone before the trainer's
            # starts: the chip is exclusive.
            serve.shutdown()
        train_phase(check, n, model, dev_expect, report, dry)
    finally:
        ray_tpu.shutdown()
        signal.alarm(0)
    left = leftover_processes(node_hex)
    check("no worker or forkserver process left behind", not left, f"{left}")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        check("driver never initialized a JAX backend",
              not xla_bridge.backends_are_initialized())
    if check.failed:
        fail(f"{len(check.failed)} check(s) failed: {check.failed}")
    if dry:
        log("DRY RUN complete: no result line (nothing ran on a chip)")
        return
    first = report["train"]
    print(json.dumps({"ok": True, "device": {
        "platform": first["platform"], "kind": first["device_kind"],
        "count": first["devices"]}}), flush=True)


if __name__ == "__main__":
    main()
