"""Call-alone timings of ``ops/moe.py::held_experts_ffn`` on the chip at the
served models' CHUNK shapes (and, for Nemotron-H, whose decode step makes
2,112 pairs, at that step's too): what decided the compact path's layout
and row tile.

    chiprun -- python3 microbench_moe.py                   # the table
    chiprun -- python3 microbench_moe.py --pieces          # and its parts
    chiprun -- python3 microbench_moe.py --check           # against float32

A row is one variant of one layer's call, the experts stacked over the
model's layers and the layer a traced index, as the models hand them in:

* ``all_pairs``: every (token, expert) pair sorted, gathered, multiplied
  and gathered back (the path before PR 50, and a decode step's still);
* ``held_ragged``: the held pairs only, each expert's rows on tiles of
  their own, the matmuls ``jax.lax.ragged_dot`` over the padded groups;
* ``held_tile_<m>``: the same over ``ops/grouped_matmul.py`` at a row tile
  of ``m`` (``held_rows`` picks one of them from the shape: ``chosen``).

A time is the host clock over ``--calls`` back-to-back calls closed by one
``block_until_ready`` (a call is 1-10 ms of device time; a part under ~0.2
ms reads the dispatch, not the device). ``--skew`` makes every token choose
one held expert, so the call takes several passes. ``--pieces`` times the
sort, the gather, the matmuls and the way back alone. ``weights_ms`` is the
read of the held experts' three matrices once at the chip's bandwidth: the
floor of a call. Rows go to ``chiprun_out/moe_sweep.jsonl``; nothing here
runs off the chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import moe_decode
from ray_tpu.ops import grouped_matmul, moe

# The cells' chunk shapes: tokens, the router, the held experts, the widths
# and the expert layers stacked (``benchmarks/configs/<name>.json``).
SHAPES = {
    "command-a-plus": dict(
        tokens=2048, dim=4096, mlp=4096, held=16, layers=4,
        router=moe.Router(experts=128, top_k=8, renormalise=True,
                          score="sigmoid")),
    "deepseek-v2": dict(
        tokens=2048, dim=5120, mlp=1536, held=40, layers=4,
        router=moe.Router(experts=160, top_k=6, groups=8, top_groups=3,
                          scale=16.0)),
    "mimo-v2.5": dict(
        tokens=2048, dim=4096, mlp=2048, held=16, layers=6,
        router=moe.Router(experts=256, top_k=8, renormalise=True,
                          score="sigmoid")),
    # Experts that are NOT SwiGLUs (two leaves, a squared ReLU) in a latent
    # width of 1,024: a chunk's 45,056 pairs and a decode step's 2,112 (96
    # slots), of which a quarter are held.
    "nemotron_h": dict(
        tokens=2048, dim=1024, mlp=2688, held=128, layers=5, gated=False,
        router=moe.Router(experts=512, top_k=22, renormalise=True,
                          scale=5.0, score="sigmoid")),
    "nemotron_h.decode": dict(
        tokens=96, dim=1024, mlp=2688, held=128, layers=5, gated=False,
        router=moe.Router(experts=512, top_k=22, renormalise=True,
                          scale=5.0, score="sigmoid")),
}
ROW_TILES = (64, 128, 256, 512)
HBM_BYTES_PER_S = 819e9


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


def _inputs(shape, seed, skew, dtype=jnp.bfloat16):
    s = shape
    keys = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(keys[0], (s["tokens"], s["dim"]),
                          jnp.float32).astype(dtype)

    def leaf(key, a, b):
        # A layer at a time in ``dtype``: Nemotron-H's stack is 3.5 GB a
        # leaf, and a float32 draw of it whole would not fit.
        return moe_decode.init_leaves(
            {"w": ((s["layers"], s["held"], a, b), a)}, key, dtype)["w"]

    experts = {"w_up": leaf(keys[2], s["dim"], s["mlp"]),
               "w_down": leaf(keys[3], s["mlp"], s["dim"])}
    if s.get("gated", True):
        experts["w_gate"] = leaf(keys[1], s["dim"], s["mlp"])
    logits = jax.random.normal(keys[4], (s["tokens"], s["router"].experts),
                               jnp.float32)
    if skew:
        logits = logits.at[:, 3].add(20.0)
    idx, weights = moe.route(logits, s["router"])
    return x, idx, weights, experts, jnp.int32(s["layers"] - 2)


def _plan(shape, tile=None):
    """``(cap, tile)`` as ``held_rows`` gives them, or at another tile: a
    tile a held expert for every ``tile`` rows of its balanced share and a
    quarter more, and a quarter more tiles. Where ``held_rows`` keeps every
    pair (a decode step's call) the smallest tile stands in, so that the
    other way can be timed beside it."""
    s = shape
    pairs, held = s["tokens"] * s["router"].top_k, s["held"]
    if tile is None:
        return moe.held_rows(pairs, held, s["router"].experts) \
            or _plan(shape, ROW_TILES[0])
    want = pairs * moe._SLACK / s["router"].experts
    return (math.ceil(held * moe._SLACK) * math.ceil(want / tile) * tile,
            tile)


def _padded_groups(stack, tile_group, live_tiles, tile):
    """The tiles' owners as ``ragged_dot``'s group sizes: every live tile
    whole, padding and all."""
    tiles = tile_group.shape[0]
    return jnp.zeros((stack["w_down"].shape[0],), jnp.int32).at[
        tile_group].add(jnp.where(jnp.arange(tiles) < live_tiles, tile, 0))


def _ragged_three(xs, stack, groups):
    """An expert's matmuls, ragged: three of a SwiGLU, two of a squared
    ReLU."""
    up = jax.lax.ragged_dot(xs, stack["w_up"], groups)
    if "w_gate" in stack:
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(xs, stack["w_gate"], groups)) * up
    else:
        hidden = jnp.square(jax.nn.relu(up))
    return jax.lax.ragged_dot(hidden, stack["w_down"], groups)


def _ragged(xs, stack, tile_group, live_tiles, tile):
    return _ragged_three(
        xs, stack, _padded_groups(stack, tile_group, live_tiles, tile))


def variants(shape):
    """name -> ``fn(x, idx, weights, experts, layer) -> (y, sizes)``."""
    held = (0, shape["held"])

    def all_pairs(x, idx, w, experts, layer):
        return moe._all_pairs(x, idx, w, experts, held, None, layer)

    def held_pairs(plan, **kw):
        def fn(x, idx, w, experts, layer):
            return moe._held_pairs(x, idx, w, experts, held, None, layer,
                                   *plan, **kw)
        return fn

    tiled = moe._tiled if shape.get("gated", True) else moe._tiled_relu2
    out = {"all_pairs": all_pairs,
           "held_ragged": held_pairs(_plan(shape), matmuls=_ragged)}
    for tile in ROW_TILES:
        out[f"held_tile_{tile}"] = held_pairs(_plan(shape, tile),
                                              matmuls=tiled)
    return out


def pieces(shape, x, idx, weights, experts, layer):
    """name -> (fn, args): the parts of a balanced call, alone, at the
    layout ``held_rows`` picks."""
    s = shape
    t, k = idx.shape
    n, count = t * k, s["held"]
    cap, tile = _plan(s)
    group = jnp.where(idx < count, idx, count).reshape(-1)
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    order = jnp.argsort(group, stable=True)
    # The layout of ``_held_pairs``'s one pass, made here once.
    tiles_of = -(-sizes // tile)
    tile_ends = jnp.cumsum(tiles_of)
    tile_id = jnp.arange(cap // tile, dtype=jnp.int32)
    owner = jnp.minimum(jnp.searchsorted(tile_ends, tile_id, side="right"),
                        count - 1)
    first_row = (tile_id - (tile_ends - tiles_of)[owner]) * tile
    tile_rows = jnp.where(tile_id < tile_ends[-1],
                          jnp.clip(sizes[owner] - first_row, 0, tile), 0)
    rank = ((jnp.cumsum(sizes) - sizes)[owner] + first_row)[:, None] \
        + jnp.arange(tile, dtype=jnp.int32)
    pair = order[jnp.minimum(rank, n - 1)].reshape(-1)
    token, live_tiles = pair // k, tile_ends[-1]
    tile_group = layer * count + owner
    xs = x[token]
    rows_w = weights.reshape(-1)[pair]

    def scatter_add(rows, w, token, tile_rows):
        live = (jnp.arange(tile)[None, :] < tile_rows[:, None]).reshape(-1)
        part = jnp.where(live[:, None],
                         rows.astype(jnp.float32) * w[:, None], 0.0)
        return jnp.zeros((t, s["dim"]), jnp.float32).at[token].add(part)

    # The stack is seen as groups INSIDE the timed program: outside one it
    # is a copy of every held expert's matrices.
    def all_pairs_three(xs, experts, sizes, layer):
        return _ragged_three(xs, *moe._stacked(experts, sizes, layer, count))

    def held_three(matmuls, xs, experts, tile_group, live_tiles):
        return matmuls(xs, moe._as_groups(experts, count), tile_group,
                       live_tiles, tile)

    return {
        "two_argsorts": (lambda g: jnp.argsort(jnp.argsort(g, stable=True)),
                         (group,)),
        "one_sort": (lambda g: jnp.sort(
            g * n + jnp.arange(n, dtype=jnp.int32)), (group,)),
        "gather_all_pairs": (lambda x, o: x[o // k], (x, order)),
        "gather_held": (lambda x, token: x[token], (x, token)),
        "three_ragged_all_pairs": (all_pairs_three,
                                   (x[order // k], experts, sizes, layer)),
        "three_ragged_held": (functools.partial(held_three, _ragged),
                              (xs, experts, tile_group, live_tiles)),
        "three_tiled_held": (
            functools.partial(held_three, moe._tiled if s.get("gated", True)
                              else moe._tiled_relu2),
            (xs, experts, tile_group, live_tiles)),
        "add_rows_held": (
            functools.partial(grouped_matmul.add_rows, tokens=t,
                              block_m=tile),
            (xs, rows_w, token, tile_rows, live_tiles)),
        "scatter_add_held": (scatter_add, (xs, rows_w, token, tile_rows)),
        "gather_back_all_pairs": (
            lambda o, order, w: (o[jnp.argsort(order)].reshape(t, k, -1)
                                 .astype(jnp.float32) * w[..., None]).sum(1),
            (x[order // k], order, weights)),
    }


def _reference(x, idx, weights, experts, layer, count):
    """The held experts' part in float32, an expert at a time."""
    x = x.astype(jnp.float32)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(count):
        w = jnp.where(idx == e, weights, 0.0).sum(-1)           # (T,)
        up, down = (experts[n][layer, e].astype(jnp.float32)
                    for n in ("w_up", "w_down"))
        with jax.default_matmul_precision("highest"):
            if "w_gate" in experts:
                h = jax.nn.silu(x @ experts["w_gate"][layer, e].astype(
                    jnp.float32)) * (x @ up)
            else:
                h = jnp.square(jax.nn.relu(x @ up))
            y = y + w[:, None] * (h @ down)
    return y


def check(names, seed):
    """Every variant against float32 at every shape, balanced and skewed:
    its largest error over the reference's largest value, which bfloat16
    operands keep under 2%. Returns the exit code."""
    bad = 0
    for name in names:
        shape = SHAPES[name]
        for skew in (False, True):
            args = _inputs(shape, seed, skew)
            ref = np.asarray(_reference(*args, shape["held"]))
            sizes_ref = np.bincount(np.asarray(args[1]).reshape(-1),
                                    minlength=shape["router"].experts
                                    )[:shape["held"]]
            for variant, fn in variants(shape).items():
                y, sizes = jax.jit(fn)(*args)
                err = float(np.abs(np.asarray(y, np.float32) - ref).max()
                            / np.abs(ref).max())
                ok = err < 0.02 and (np.asarray(sizes) == sizes_ref).all()
                bad += not ok
                print(json.dumps({
                    "check": name, "skew": skew, "variant": variant,
                    "pairs_held": int(sizes_ref.sum()),
                    "rel_err": round(err, 5), "ok": bool(ok)}), flush=True)
            args = None          # the next stack needs the room
    return 1 if bad else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skew", action="store_true")
    ap.add_argument("--pieces", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default="chiprun_out/moe_sweep.jsonl")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("microbench_moe.py times the chip's kernels; "
                         f"this backend is {jax.default_backend()}")
    names = a.shapes.split(",")
    if a.check:
        raise SystemExit(check(names, a.seed))
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    sink = open(a.out, "a")

    def emit(row):
        row.update(device=jax.devices()[0].device_kind, skew=a.skew)
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    for name in names:
        shape = SHAPES[name]
        args = _inputs(shape, a.seed, a.skew)
        weights_ms = ((3 if shape.get("gated", True) else 2)
                      * shape["held"] * shape["dim"] * shape["mlp"] * 2
                      / HBM_BYTES_PER_S * 1e3)
        todo = {v: (fn, args) for v, fn in variants(shape).items()}
        if a.pieces:
            todo.update(pieces(shape, *args))
        for variant, (fn, fn_args) in todo.items():
            try:
                ms = _time(fn, fn_args, a.calls)
            except Exception as e:   # a tile Mosaic refuses is a row too
                emit({"shape": name, "variant": variant,
                      "error": str(e)[:300]})
                continue
            emit({"shape": name, "variant": variant, "ms": round(ms, 4),
                  "pairs": shape["tokens"] * shape["router"].top_k,
                  "chosen": list(moe.held_rows(
                      shape["tokens"] * shape["router"].top_k,
                      shape["held"], shape["router"].experts) or ()),
                  "weights_ms": round(weights_ms, 4)})
        args = todo = fn = fn_args = None   # the next stack needs the room


if __name__ == "__main__":
    main()
