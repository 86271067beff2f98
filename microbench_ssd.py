"""Kernel-alone timings of the Mamba-2 state-space duality on the chip
(``ray_tpu/ops/ssd.py``) at the shapes ``nemotron-3-super.agent_turns_batch``
runs: 128 heads of 64 over 8 groups, a state of 128 a channel, bfloat16
operands.

    chiprun -- python3 microbench_ssd.py            # both forms
    chiprun -- python3 microbench_ssd.py --check

* **step**: one decode token in each of ``--slots`` (96) slots of
  ``--layers`` (5) layers' state leaf, the Pallas kernel (``ssd_step``)
  beside the same expression left to XLA (``tests/test_ssd.py::step_jnp``),
  with every slot stepping and with some outside the step, at ``--blocks``
  heads a grid step and ``--unrolls`` heads of its loop written out.
  ``share`` is the stepping slots' state (float32, read and written: 8.39
  MB a slot a layer) over the time x 819 GB/s.
* **chunk**: ``--rows`` (1, 2, 4) rows of ``--buckets`` (256, 1,024, 2,048)
  positions from a state, as far as the engine's wave cap (``--tokens-max``
  4,096), every row full and every row half real (``lengths``): the Pallas
  kernel (``ssd_chunk``) beside XLA's form of the same sum
  (``tests/test_ssd.py::chunk_jnp``). ``share`` is the chunked form's
  matmuls over the REAL positions at the published sub-chunk of 128 (a
  token a head: 2 x 128 x 64 inside the sub-chunk, 2 x 64 x 128 out of the
  state and as much into it, a group's ``C B^T`` 2 x 128 x 128 / 16:
  51,200) over the time x 197 TFLOP/s, the benchmark's count
  (``benchmarks/nemotron_h_counts.py``). Called so, ``x`` and ``y`` are
  four-dimensional arrays in HBM (a head's 64 channels padded to 128
  lanes) and both forms pay for it, the kernel a copy each way.
* **chunk_as_layer**: one row of ``--tokens`` (2,048) as
  ``nemotron_h_decode.paged_prefill_suffix`` calls it: ``x``, ``B`` and
  ``C`` cut from the convolution's flat output, the row's state gathered
  from a leaf and scattered back, ``y`` into the gate and the group norm.
  This is the reading that predicts the engine's: there XLA's form stacks
  its scan's ``y`` in the gate's layout (4.98 ms against 0.83 alone, PR
  58) and the kernel reads and writes the flat arrays where they lie.

A time is the host clock over ``--calls`` back-to-back calls closed by one
``block_until_ready``; the state is donated and handed on, as the engine
does. ``--check`` holds both forms to the recurrence, position by position
in float32 at the highest precision, over a prompt of three chunks and
eight decode steps. Rows go to ``chiprun_out/ssd_sweep.jsonl``; nothing
here runs off the chip (``--cpu`` rehearses the control flow at toy sizes
and times nothing)."""

from __future__ import annotations

import argparse
import json
import os
import time

HBM, PEAK = 819e9, 197e12


def _time(fn, state, args, calls):
    """Seconds a call; ``fn(state, *args) -> (out, state)``."""
    import jax

    for _ in range(2):
        out, state = fn(state, *args)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        out, state = fn(state, *args)
    jax.block_until_ready((out, state))
    return (time.perf_counter() - t0) / calls, state


def chunk_flops(tokens: int, heads: int, head_dim: int, groups: int,
                state: int, sub: int = 128) -> float:
    """The chunked form's matmuls at sub-chunks of ``sub``."""
    per_head = 2.0 * sub * head_dim + 4.0 * head_dim * state
    return tokens * (heads * per_head + groups * 2.0 * sub * state)


def recurrence(x, dt, A, Bm, Cm, D, S):
    """``ssd`` position by position (``lax.scan`` over time), float32."""
    import jax
    import jax.numpy as jnp

    K = x.shape[2] // Bm.shape[2]

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        b = jnp.repeat(b_t, K, axis=1)
        c = jnp.repeat(c_t, K, axis=1)
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * b[:, :, None, :]
        return S, jnp.sum(S * c[:, :, None, :], -1) + D[None, :, None] * x_t

    S, y = jax.lax.scan(step, S, tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), S


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=96)
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--buckets", default="256,1024,2048",
                    help="positions a row of the chunk forms to time")
    ap.add_argument("--rows", default="1,2,4",
                    help="rows of a chunk call (rows x bucket <= "
                    "--tokens-max, the engine's wave cap)")
    ap.add_argument("--tokens-max", type=int, default=4096)
    ap.add_argument("--blocks", default="32,64",
                    help="heads a grid step of the step kernel to time")
    ap.add_argument("--unrolls", default="1,4",
                    help="unrolls of the kernel's loop over heads to time")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ssd_sweep.jsonl")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd
    from tests.test_ssd import chunk_jnp, step_jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        raise SystemExit("microbench_ssd.py times the chip; --cpu rehearses "
                         "its control flow")
    H, P, G, N = (128, 64, 8, 128) if not args.cpu else (8, 4, 2, 16)
    B, L, T = ((args.slots, args.layers, args.tokens) if not args.cpu
               else (3, 2, 64))
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 12)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rows = []

    def say(row):
        if args.cpu:   # a rehearsal times nothing
            row = {k: v for k, v in row.items()
                   if not k.startswith(("ms", "share_"))}
        row.update(device=dev.device_kind)
        rows.append(row)
        print(json.dumps(row), flush=True)

    A = -jax.random.uniform(keys[0], (H,), jnp.float32, 1.0, 16.0)
    D = 1.0 + 0.1 * jax.random.normal(keys[1], (H,), jnp.float32)

    def steps_of(key, shape):
        return jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * 4.6 - 6.9)                  # 1e-3 .. 1e-1

    # ------------------------------------------------------------- step
    x = jax.random.normal(keys[2], (B, H, P)).astype(bf)
    dt = steps_of(keys[3], (B, H))
    bm = jax.random.normal(keys[4], (B, G, N)).astype(bf)
    cm = jax.random.normal(keys[5], (B, G, N)).astype(bf)
    slot_bytes = H * P * N * 4

    def stepper(form, **kw):
        def run(S, x, dt, bm, cm, live):
            y = 0.0
            for layer in range(L):
                rows_ = layer * (B + 1) + jnp.where(
                    live, jnp.arange(B, dtype=jnp.int32), B)
                yl, S = form(x, dt, A, bm, cm, D, S, rows_, live, **kw)
                y = y + yl
            return y, S
        return jax.jit(run, donate_argnums=(0,))

    forms = [(f"pallas/{b}/{u}", ssd.ssd_step,
              {"block": int(b), "unroll": int(u)})
             for b in (args.blocks.split(",") if not args.cpu else ["4"])
             for u in (args.unrolls.split(",") if not args.cpu else ["1"])]
    forms.append(("xla", step_jnp, {}))
    for name, form, kw in forms:
        for live_n in sorted({B, max(1, B - B // 8), max(1, B // 2)},
                             reverse=True):
            live = jnp.arange(B) < live_n
            state = jnp.zeros((L * (B + 1), H, P, N), jnp.float32)
            sec, state = _time(stepper(form, **kw), state,
                               (x, dt, bm, cm, live), args.calls)
            del state
            say({"form": "step", "impl": name, "slots": B,
                 "stepping": live_n, "layers": L,
                 "ms_a_layer": sec / L * 1e3,
                 "share_of_hbm_pct": 100 * 2 * live_n * slot_bytes * L
                 / (sec * HBM)})

    # ------------------------------------------------------------ chunk
    chunk_forms = [("pallas", ssd.ssd_chunk), ("xla", chunk_jnp)]
    sub = 128 if not args.cpu else 8

    def chunk_inputs(rows, bucket):
        return (jax.random.normal(keys[6], (rows, bucket, H, P)).astype(bf),
                steps_of(keys[7], (rows, bucket, H)),
                jax.random.normal(keys[8], (rows, bucket, G, N)).astype(bf),
                jax.random.normal(keys[9], (rows, bucket, G, N)).astype(bf))

    shapes = [(int(r), int(b)) for b in args.buckets.split(",")
              for r in args.rows.split(",")
              if int(r) * int(b) <= args.tokens_max] if not args.cpu \
        else [(2, 32)]
    for rows_n, bucket in shapes:
        inputs = chunk_inputs(rows_n, bucket)
        for real in ("full", "half"):
            n_real = jnp.full((rows_n,), bucket if real == "full"
                              else bucket // 2, jnp.int32)
            flops = chunk_flops(int(n_real.sum()), H, P, G, N)
            for name, form in chunk_forms:
                fn = jax.jit(lambda S, x, dt, bm, cm, form=form: form(
                    x, dt, A, bm, cm, D, S, n_real, sub),
                    donate_argnums=(0,))
                state = jnp.zeros((rows_n, H, P, N), jnp.float32)
                sec, state = _time(fn, state, inputs, args.calls)
                del state
                say({"form": "chunk", "impl": name, "rows": rows_n,
                     "bucket": bucket, "lengths": real, "sub_chunk": sub,
                     "ms": sec * 1e3,
                     "share_of_peak_pct": 100 * flops / (sec * PEAK)})

    # As the layer calls it (``nemotron_h_decode.paged_prefill_suffix``):
    # the inputs cut from the convolution's flat output, the row's state
    # gathered from the leaf and scattered back, ``y`` into the gate and
    # the group norm: what the call costs where XLA fuses round it.
    T = args.tokens if not args.cpu else 32
    slots, di = 4, H * P
    xbc = jax.random.normal(keys[10], (1, T, di + 2 * G * N)).astype(bf)
    z = jax.random.normal(keys[11], (1, T, di)).astype(bf)
    dtl = steps_of(keys[7], (1, T, H))
    n_real = jnp.full((1,), T, jnp.int32)
    row = jnp.asarray([1], jnp.int32)
    for name, form in chunk_forms:
        def layer(leaf, xbc, z, dt, form=form):
            xs = xbc[..., :di].reshape(1, T, H, P)
            bm = xbc[..., di:di + G * N].reshape(1, T, G, N)
            cm = xbc[..., di + G * N:].reshape(1, T, G, N)
            y, s1 = form(xs, dt, A, bm, cm, D, leaf[row], n_real, sub)
            y = y.reshape(1, T, di) * jax.nn.silu(z.astype(jnp.float32))
            yg = y.reshape(1, T, G, -1)
            yg = yg * jax.lax.rsqrt(
                jnp.mean(jnp.square(yg), -1, keepdims=True) + 1e-5)
            return yg.reshape(1, T, di).astype(bf), leaf.at[row].set(s1)
        leaf = jnp.zeros((slots + 1, H, P, N), jnp.float32)
        sec, leaf = _time(jax.jit(layer, donate_argnums=(0,)), leaf,
                          (xbc, z, dtl), args.calls)
        del leaf
        say({"form": "chunk_as_layer", "impl": name, "rows": 1,
             "bucket": T, "lengths": "full", "sub_chunk": sub,
             "ms": sec * 1e3,
             "share_of_peak_pct": 100 * chunk_flops(T, H, P, G, N)
             / (sec * PEAK)})

    # ------------------------------------------------------------ check
    if args.check:
        n_chunks, n_steps = 3, 8
        Tc = T if not args.cpu else 16
        total = n_chunks * Tc + n_steps
        kk = jax.random.split(jax.random.key(1), 4)
        xa = jax.random.normal(kk[0], (1, total, H, P)).astype(bf)
        da = steps_of(kk[1], (1, total, H))
        ba = jax.random.normal(kk[2], (1, total, G, N)).astype(bf)
        ca = jax.random.normal(kk[3], (1, total, G, N)).astype(bf)
        with jax.default_matmul_precision("highest"):
            want, _ = jax.jit(recurrence)(
                xa, da, A, ba, ca, D, jnp.zeros((1, H, P, N), jnp.float32))
        scale_of = float(jnp.abs(want).max())
        ys = {}
        for name, form in chunk_forms:
            chunk = jax.jit(lambda S, x, dt, bm, cm, form=form: form(
                x, dt, A, bm, cm, D, S, None, sub))
            got, S = [], jnp.zeros((1, H, P, N), jnp.float32)
            for c in range(n_chunks):
                sl = slice(c * Tc, (c + 1) * Tc)
                y, S = chunk(S, xa[:, sl], da[:, sl], ba[:, sl], ca[:, sl])
                got.append(y[0])
            ys[name] = jnp.concatenate(got)
            err = float(jnp.abs(ys[name] - want[0, :n_chunks * Tc]).max())
            say({"check": "chunk", "impl": name, "tokens": n_chunks * Tc,
                 "max_abs_err": err, "max_abs": scale_of})
            if not err < 0.02 * scale_of:
                raise SystemExit(f"{name} chunk leaves the recurrence")
        # The kernel against XLA's form, rounding for rounding.
        off = jnp.abs(ys["pallas"] - ys["xla"])
        say({"check": "chunk", "impl": "pallas - xla",
             "max_abs_err": float(off.max()),
             "share_over_1e-5": float((off > 1e-5 * scale_of).mean()),
             "max_abs": scale_of})
        one = jnp.ones((1,), bool)
        for name, form in (("pallas", ssd.ssd_step), ("xla", step_jnp)):
            leaf = jnp.zeros((2, H, P, N), jnp.float32).at[0].set(S[0])
            worst = 0.0
            for t in range(n_chunks * Tc, total):
                y, leaf = form(xa[:, t], da[:, t], A, ba[:, t], ca[:, t], D,
                               leaf, jnp.zeros((1,), jnp.int32), one)
                worst = max(worst, float(jnp.abs(y[0] - want[0, t]).max()))
            say({"check": "step", "impl": name, "steps": n_steps,
                 "max_abs_err": worst, "max_abs": scale_of})
            if not worst < 0.02 * scale_of:
                raise SystemExit(f"{name} step leaves the recurrence")
    with open(args.out, "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
