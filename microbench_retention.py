"""Kernel-alone timings of power retention on the chip
(``ray_tpu/ops/power_retention.py``) at the shapes
``brumby-14b.long_context_batch`` runs: 8 key-value heads of 128 under 40
query heads, a state of 9,216 rows a head, bfloat16 operands.

    chiprun -- python3 microbench_retention.py            # both forms
    chiprun -- python3 microbench_retention.py --check

* **step**: one decode token in each of ``--slots`` (16) slots of
  ``--layers`` (2) layers' state leaves, the Pallas kernel
  (``retention_step``) beside the same expression left to XLA
  (``tests/test_power_retention.py::step_jnp``), and with some slots
  outside the step.
  ``share`` is the stepping slots' state as held (``S`` and ``z``, float32,
  read and written: 76.1 MB a slot a layer) over the time x 819 GB/s.
* **chunk**: one row of ``--tokens`` (2,048) positions from a state
  (``retention_chunk``), at sub-chunks of ``--subs``. ``share`` is the
  recurrent form's operations over the published 8,256 monomials (2 x 8,256
  x 128 x 48 a token) over the time x 197 TFLOP/s, the benchmark's count
  (``benchmarks/brumby_counts.py``).

A time is the host clock over ``--calls`` back-to-back calls closed by one
``block_until_ready``; the state is donated and handed on, as the engine
does. ``--check`` holds both forms to the reference's quadratic form
(``benchmarks/reference/brumby_ref.retention_pairs``, float32 at the
highest precision) over a prompt of three chunks and eight decode steps.
Rows go to ``chiprun_out/retention_sweep.jsonl``; nothing here runs off the
chip (``--cpu`` rehearses the control flow at toy sizes and times
nothing)."""

from __future__ import annotations

import argparse
import json
import os
import time

HBM, PEAK = 819e9, 197e12


def _time(fn, state, args, calls):
    """Seconds a call; ``fn(*state, *args) -> (out, *state)``."""
    import jax

    for _ in range(2):
        out, *state = fn(*state, *args)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        out, *state = fn(*state, *args)
    jax.block_until_ready((out, state))
    return (time.perf_counter() - t0) / calls, state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--subs", default="64,128,256",
                    help="sub-chunk lengths of the chunk form to time")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/retention_sweep.jsonl")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as pr
    from tests.test_power_retention import step_jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        raise SystemExit("microbench_retention.py times the chip; --cpu "
                         "rehearses its control flow")
    J, G, d, block = (8, 5, 128, 16) if not args.cpu else (2, 3, 16, 4)
    B, L, T = ((args.slots, args.layers, args.tokens) if not args.cpu
               else (3, 2, 64))
    R = pr.phi_rows(d, block)
    scale = d ** -0.5
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 8)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rows = []

    def say(row):
        if args.cpu:   # a rehearsal times nothing
            row = {k: v for k, v in row.items()
                   if not k.startswith(("ms", "share_"))}
        row.update(device=dev.device_kind)
        rows.append(row)
        print(json.dumps(row), flush=True)

    def head_norm(x):
        x = x.astype(jnp.float32)
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + 1e-6)).astype(dt)

    # ------------------------------------------------------------- step
    q = head_norm(jax.random.normal(keys[0], (B, J, G, d)))
    k = head_norm(jax.random.normal(keys[1], (B, J, d)))
    v = jax.random.normal(keys[2], (B, J, d)).astype(dt)
    lg = jnp.log(jax.random.uniform(keys[3], (B, J), jnp.float32, 0.9,
                                    0.999))
    slot_bytes = J * (d + 1) * R * 4

    def stepper(form):
        def run(S, z, q, k, v, lg, steps):
            o = 0.0
            for layer in range(L):
                ol, S, z = form(q, k, v, lg, S, z, steps, layer,
                                scale=scale, block=block)
                o = o + ol
            return o, S, z
        return jax.jit(run, donate_argnums=(0, 1))

    for name, form in (("pallas", pr.retention_step),
                       ("xla", step_jnp)):
        for live in sorted({B, max(1, B - 2), max(1, B // 2)}, reverse=True):
            steps = jnp.arange(B) < live
            state = [jnp.zeros((L, B + 1, J, d, R), jnp.float32),
                     jnp.zeros((L, B + 1, J, R), jnp.float32)]
            sec, state = _time(stepper(form), state, (q, k, v, lg, steps),
                               args.calls)
            del state
            say({"form": "step", "impl": name, "slots": B, "stepping": live,
                 "layers": L, "ms_a_layer": sec / L * 1e3,
                 "share_of_hbm_pct": 100 * 2 * live * slot_bytes * L
                 / (sec * HBM)})

    # ------------------------------------------------------------ chunk
    qc = head_norm(jax.random.normal(keys[4], (1, T, J, G, d)))
    kc = head_norm(jax.random.normal(keys[5], (1, T, J, d)))
    vc = jax.random.normal(keys[6], (1, T, J, d)).astype(dt)
    lgc = jnp.log(jax.random.uniform(keys[7], (1, T, J), jnp.float32, 0.9,
                                     0.999))
    flops = T * 2.0 * (d * (d + 1) // 2) * d * (J + J * G)
    for sub in ([int(x) for x in args.subs.split(",")] if not args.cpu
                else (16,)):
        fn = jax.jit(lambda S, z, q, k, v, lg, sub=sub: pr.retention_chunk(
            q, k, v, lg, S, z, scale=scale, block=block, sub_chunk=sub),
            donate_argnums=(0, 1))

        state = [jnp.zeros((1, J, d, R), jnp.float32),
                 jnp.zeros((1, J, R), jnp.float32)]
        sec, state = _time(fn, state, (qc, kc, vc, lgc), args.calls)
        del state
        say({"form": "chunk", "tokens": T, "sub_chunk": sub,
             "ms": sec * 1e3, "share_of_peak_pct": 100 * flops
             / (sec * PEAK)})

    # ------------------------------------------------------------ check
    if args.check:
        from benchmarks.reference import brumby_ref

        n_chunks, n_steps = 3, 8
        Tc = T if not args.cpu else 32
        total = n_chunks * Tc + n_steps
        kk = jax.random.split(jax.random.key(1), 4)
        qa = head_norm(jax.random.normal(kk[0], (1, total, J, G, d)))
        ka = head_norm(jax.random.normal(kk[1], (1, total, J, d)))
        va = jax.random.normal(kk[2], (1, total, J, d)).astype(dt)
        la = jnp.log(jax.random.uniform(kk[3], (1, total, J), jnp.float32,
                                        0.9, 0.999))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda q, k, v, lg: brumby_ref.retention_pairs(
                q, k, v, lg, scale, pr.EPS))(
                    *(a[0].astype(jnp.float32) for a in (qa, ka, va)), la[0])
        S = jnp.zeros((1, 2, J, d, R), jnp.float32)
        z = jnp.zeros((1, 2, J, R), jnp.float32)
        chunk = jax.jit(lambda S, z, q, k, v, lg: pr.retention_chunk(
            q, k, v, lg, S, z, scale=scale, block=block))
        got = []
        Sr, zr = S[0, :1], z[0, :1]
        for c in range(n_chunks):
            sl = slice(c * Tc, (c + 1) * Tc)
            o, Sr, zr = chunk(Sr, zr, qa[:, sl], ka[:, sl], va[:, sl],
                              la[:, sl])
            got.append(o[0])
        scale_of = float(jnp.abs(want).max())
        err = float(jnp.abs(jnp.concatenate(got) - want[:n_chunks * Tc]
                            ).max())
        say({"check": "chunk", "tokens": n_chunks * Tc,
             "max_abs_err": err, "max_abs": scale_of})
        one = jnp.ones((1,), bool)
        for name, form in (("pallas", pr.retention_step),
                           ("xla", step_jnp)):
            Sl = S.at[0, 0].set(Sr[0])
            zl = z.at[0, 0].set(zr[0])
            worst = 0.0
            for t in range(n_chunks * Tc, total):
                o, Sl, zl = form(qa[:, t], ka[:, t], va[:, t], la[:, t], Sl,
                                 zl, one, 0, scale=scale, block=block)
                worst = max(worst, float(jnp.abs(o[0] - want[t]).max()))
            say({"check": "step", "impl": name, "steps": n_steps,
                 "max_abs_err": worst, "max_abs": scale_of})
            if not worst < 0.05 * scale_of:
                raise SystemExit(f"{name} step leaves the reference")
        if not err < 0.05 * scale_of:
            raise SystemExit("the chunked form leaves the reference")
    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
