"""Kernel-alone timings of the three flash-attention training kernels on the
chip: the sweep ``ray_tpu/ops/flash_attention.py::_PREFERRED`` was set from.

    chiprun -- python3 microbench_flash.py                 # the sweep
    chiprun -- python3 microbench_flash.py --tiles 512x512 --variants

Each kernel runs alone (``_fwd``, ``_bwd_dkv``, ``_bwd_dq`` on inputs already
in (B, H, S, D)), causal, at ``internlm2-1.8b.pretrain_fsdp4``'s shape by
default; a time is the host clock over ``--calls`` back-to-back calls closed
by one ``block_until_ready`` (a call is 1-3 ms of device time, the dispatch
some 50 us). ``--variants`` times what the schedule saves at one tile size:
every live tile masked, and dead tiles visited as steps that fetch nothing.
``--against <flash_attention.py>`` times another checkout's module beside
this one (its ``_fwd`` and its whole ``_bwd``); ``--reference`` holds both to
plain float32 attention (output and the three gradients). Rows go to
``chiprun_out/flash_sweep.jsonl``; nothing here runs off the chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import flash_attention as fa


def _time(fn, args, calls):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def _inputs(batch, heads, kv_heads, seq, d, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = [(batch, heads, seq, d), (batch, kv_heads, seq, d),
              (batch, kv_heads, seq, d), (batch, heads, seq, d)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(keys, shapes)]


def _all_masked(schedule):
    """Every live tile builds the mask, as before the schedule."""
    def build(*a, **kw):
        s = schedule(*a, **kw)
        flags = np.where(s.flags & fa._LIVE, s.flags | fa._MASKED, s.flags)
        return s._replace(flags=flags.astype(np.int32))
    return build


def _dead_visited(schedule):
    """Dead tiles are grid steps that hold the last block (the clamped
    form): no fetch, no compute, one step's fixed cost each."""
    def build(by_kv, sq, sk, bq, bk, q_offset, causal, window, segmented,
              group=1):
        s = schedule(by_kv, sq, sk, bq, bk, q_offset, causal, window,
                     segmented, group)
        n_minor = (sq // bq) if by_kv else (sk // bk)
        rows = {}
        for a, b, g, f in zip(*s):
            rows.setdefault(int(a), []).append((int(b), int(g), int(f)))
        out = []
        for a, steps in rows.items():
            want = n_minor * (group if by_kv else 1)
            steps = [(b, g, f & ~(fa._FIRST | fa._LAST)) for b, g, f in steps]
            steps += [(steps[-1][0], steps[-1][1], 0)] * (want - len(steps))
            steps[0] = steps[0][:2] + (steps[0][2] | fa._FIRST,)
            steps[-1] = steps[-1][:2] + (steps[-1][2] | fa._LAST,)
            out += [(a, b, g, f) for b, g, f in steps]
        cols = list(zip(*out))
        return fa._Schedule(*(np.asarray(c, np.int32) for c in cols))
    return build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,16,8,4096,128",
                    help="batch,heads,kv_heads,seq,head_dim")
    ap.add_argument("--tiles", default="", help="e.g. 512x512,1024x512")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--against", default="")
    ap.add_argument("--reference", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("microbench_flash.py times the chip's kernels; "
                         f"this backend is {jax.default_backend()}")
    if a.check:
        raise SystemExit(_check())
    batch, heads, kv_heads, seq, d = (int(x) for x in a.shape.split(","))
    dtype = jnp.bfloat16
    q, k, v, do = _inputs(batch, heads, kv_heads, seq, d, dtype)
    scale = d ** -0.5
    out, lse = jax.jit(lambda q, k, v: fa._fwd(
        q, k, v, None, None, scale, True, None, 0))(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    if a.tiles:
        tiles = [tuple(int(x) for x in t.split("x"))
                 for t in a.tiles.split(",")]
    else:
        tiles = [(bq, bk) for bq, bk in itertools.product(
            (256, 512, 1024), (256, 512, 1024, 2048))
            if bq <= seq and bk <= seq]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    sink = open(a.out, "a")

    def emit(row):
        row.update(shape=a.shape, device=jax.devices()[0].device_kind)
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    def kernels(bq, bk):
        return {
            "flash_fwd": (lambda q, k, v: fa._fwd(
                q, k, v, None, None, scale, True, None, 0, bq, bk),
                (q, k, v)),
            "flash_bwd_dkv": (lambda q, k, v, do, lse, delta: fa._bwd_dkv(
                q, k, v, None, None, do, lse, delta, scale, True, None, 0,
                bq, bk), (q, k, v, do, lse, delta)),
            "flash_bwd_dq": (lambda q, k, v, do, lse, delta: fa._bwd_dq(
                q, k, v, None, None, do, lse, delta, scale, True, None, 0,
                bq, bk), (q, k, v, do, lse, delta)),
        }

    def run(bq, bk, variant):
        for name, (fn, args) in kernels(bq, bk).items():
            try:
                ms = _time(fn, args, a.calls)
            except Exception as e:  # a tile Mosaic refuses is a row too
                emit({"kernel": name, "block_q": bq, "block_k": bk,
                      "variant": variant, "error": str(e)[:300]})
                continue
            emit({"kernel": name, "block_q": bq, "block_k": bk,
                  "variant": variant, "ms": round(ms, 4)})

    for bq, bk in tiles:
        run(bq, bk, "as_committed")
    if a.variants:
        schedule = fa._schedule
        for variant, wrap in (("all_masked", _all_masked),
                              ("dead_visited", _dead_visited)):
            fa._schedule = wrap(schedule)
            for bq, bk in tiles:
                run(bq, bk, variant)
        fa._schedule = schedule
    # The whole backward as the VJP runs it (delta, both kernels).
    for bq, bk in ([(None, None)] + (tiles if a.tiles else [])):
        ms = _time(lambda q, k, v, out, lse, do: fa._bwd(
            q, k, v, None, None, out, lse, do, None, scale, True, None, 0,
            bq, bk), (q, k, v, out, lse, do), a.calls)
        emit({"kernel": "whole_bwd", "block_q": bq, "block_k": bk,
              "variant": "as_committed", "ms": round(ms, 4)})
    emit({"kernel": "chosen", **{
        n: [s["block_q"], s["block_k"], s["visited"], s["live"], s["masked"]]
        for n, s in fa.schedule_stats(seq, seq, d, dtype).items()}})
    ref = _reference(q, k, v, do, scale) if a.reference else None

    def against_reference(who, got):
        if ref is not None:
            emit({"kernel": "error_vs_float32", "variant": who,
                  "max_abs": [float(jnp.max(jnp.abs(
                      x.astype(jnp.float32) - y))) for x, y in zip(got, ref)],
                  "ref_max": [float(jnp.max(jnp.abs(y))) for y in ref]})

    against_reference("as_committed", (out,) + tuple(jax.jit(
        lambda *x: fa._bwd(*x[:3], None, None, *x[3:], None, scale, True,
                           None, 0, None, None))(q, k, v, out, lse, do)))
    if a.against:
        spec = importlib.util.spec_from_file_location("flash_other",
                                                      a.against)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        for bq, bk in [(256, 256)] + (tiles if a.tiles else []):
            ms = _time(lambda q, k, v: other._fwd(
                q, k, v, None, None, scale, True, None, 0, bq, bk),
                (q, k, v), a.calls)
            emit({"kernel": "flash_fwd", "block_q": bq, "block_k": bk,
                  "variant": "other", "ms": round(ms, 4)})
            ms = _time(lambda q, k, v, out, lse, do: other._bwd(
                q, k, v, None, None, out, lse, do, None, scale, True, None,
                0, bq, bk), (q, k, v, out, lse, do), a.calls)
            emit({"kernel": "whole_bwd", "block_q": bq, "block_k": bk,
                  "variant": "other", "ms": round(ms, 4)})
            o2, l2 = jax.jit(lambda q, k, v: other._fwd(
                q, k, v, None, None, scale, True, None, 0, bq, bk))(q, k, v)
            g1 = jax.jit(lambda *x: fa._bwd(
                *x[:3], None, None, *x[3:], None, scale, True, None, 0,
                None, None))(q, k, v, out, lse, do)
            g2 = jax.jit(lambda *x: other._bwd(
                *x[:3], None, None, *x[3:], None, scale, True, None, 0,
                bq, bk))(q, k, v, out, lse, do)
            against_reference(f"other_{bq}x{bk}", (o2,) + tuple(g2))
            emit({"kernel": "difference", "block_q": bq, "block_k": bk,
                  "variant": "other",
                  "max_abs": [float(jnp.max(jnp.abs(
                      x.astype(jnp.float32) - y.astype(jnp.float32))))
                      for x, y in zip((out, lse) + tuple(g1),
                                      (o2, l2) + tuple(g2))]})


def _check():
    """The masks the train cell does not use, through Mosaic: float32
    inputs at 2,048 (4 / 2 heads), output and gradients against plain
    attention. Returns the number of cases over 2e-2."""
    from ray_tpu.ops.flash_attention import flash_attention

    sq = sk = 2048
    keys = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(keys[0], (1, sq, 4, 128), jnp.float32)
    k = jax.random.normal(keys[1], (1, sk, 2, 128), jnp.float32)
    v = jax.random.normal(keys[2], (1, sk, 2, 128), jnp.float32)
    seg = jnp.searchsorted(jnp.asarray([300, 1100, 1700]), jnp.arange(sk),
                           side="right")[None, :]
    bad = 0
    for name, kw in {
            "causal": {}, "full": dict(causal=False),
            "window-300": dict(window=300),
            "window-300-tiles-256": dict(window=300, block_q=256,
                                         block_k=256),
            "segments": dict(segment_ids=seg),
            "segments-window": dict(segment_ids=seg, window=700),
            "offset": dict(q_offset=1024), "tiles-512x256": dict(
                block_q=512, block_k=256)}.items():
        qq = q[:, 1024:] if "q_offset" in kw else q
        rows = kw.get("q_offset", 0) + jnp.arange(qq.shape[1])[:, None]
        cols = jnp.arange(sk)[None, :]
        mask = jnp.ones((qq.shape[1], sk), bool)
        if kw.get("causal", True):
            mask &= rows >= cols
        if "window" in kw:
            mask &= rows - cols < kw["window"]
        mask = mask[None, None]
        if "segment_ids" in kw:
            mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])

        def dense(q, k, v):
            k, v = (jnp.repeat(x, 2, axis=2) for x in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           precision="highest") * 128 ** -0.5
            p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

        def flash(q, k, v):
            return flash_attention(q, k, v, **kw)

        got = jax.jit(jax.value_and_grad(
            lambda *x: jnp.sum(flash(*x) ** 2), argnums=(0, 1, 2)))(qq, k, v)
        ref = jax.jit(jax.value_and_grad(
            lambda *x: jnp.sum(dense(*x) ** 2), argnums=(0, 1, 2)))(qq, k, v)
        err = [float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))
               for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(ref))]
        bad += max(err) > 2e-2
        print(json.dumps({"check": name, "rel_err": err}), flush=True)
    return int(bad)


def _reference(q, k, v, do, scale):
    """Plain float32 causal attention and its gradients under ``do``."""
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    group = q.shape[1] // k.shape[1]

    def out(q, k, v):
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
        rows = jnp.arange(s.shape[-2])[:, None]
        s = jnp.where(rows >= jnp.arange(s.shape[-1])[None, :], s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest")

    grads = jax.jit(jax.grad(lambda q, k, v: jnp.sum(out(q, k, v) * do),
                             argnums=(0, 1, 2)))(q, k, v)
    return (jax.jit(out)(q, k, v),) + tuple(grads)


if __name__ == "__main__":
    main()
