"""Mixture-of-Experts FFN with expert parallelism (GSPMD dispatch).

SURVEY §2.4's EP row: the reference has no MoE (its role is placement);
the TPU build makes expert parallelism first-class. This is the
GShard/Switch dispatch formulation expressed as einsums so GSPMD lowers
the token->expert exchange to an all-to-all over the ``expert`` mesh axis
(SURVEY §5.8 plane 3 — declared, not hand-written):

    router logits -> top-k gates -> capacity-bounded dispatch mask
    expert_in  (E, C, D)  = dispatch^T tokens      [all-to-all]
    expert_out (E, C, D)  = per-expert FFN (batched matmul, E sharded)
    out        (T, D)     = combine expert_out     [all-to-all back]

Dropped tokens (beyond expert capacity) pass through the residual stream —
standard Switch behavior. Gates are renormalized over the selected top-k.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_matmul
from ray_tpu.parallel.sharding import constrain


def router_topk(
    logits: jax.Array,  # (T, E) fp32
    k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing with per-expert capacity. Returns
    (dispatch (T, E, C) one-hot, combine (T, E, C) gate weights)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    if k > 1:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    # k == 1 keeps the raw softmax prob (Switch Transformer): renormalizing
    # to 1.0 would cut the router out of the gradient path entirely.

    # Position of each (token, choice) in its expert's queue: cumulative
    # count of prior assignments to that expert (priority = token order).
    choice_onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
    # (T, k, E) -> flatten choices in (token-major, choice-minor) priority.
    flat = choice_onehot.reshape(t * k, e)
    positions = (jnp.cumsum(flat, axis=0) - flat).reshape(t, k, e)
    pos_in_expert = (positions * choice_onehot).sum(-1)  # (T, k)
    keep = pos_in_expert < capacity

    dispatch = (
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)[
            :, :, None, :]
        * keep[..., None, None]
    ).sum(1)  # (T, E, C)
    combine = dispatch * gate_vals.sum(1)[:, None, None] if k == 1 else (
        jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)[
            :, :, None, :]
        * (keep * gate_vals)[..., None, None]
    ).sum(1)
    return dispatch, combine


def moe_ffn(
    x: jax.Array,               # (B, S, D)
    params: Dict[str, Any],     # router (D,E); w_gate/w_up (E,D,M); w_down (E,M,D)
    top_k: int = 2,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward; returns (output (B,S,D), aux load-balance loss).

    Expert weights carry the ``expert`` logical axis so GSPMD shards the
    per-expert batched matmuls over the expert mesh axis and inserts the
    dispatch/combine all-to-alls.
    """
    b, s, d = x.shape
    e = params["router"].shape[-1]
    t = b * s
    capacity = max(1, int(capacity_factor * top_k * t / e))
    tokens = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    dispatch, combine = router_topk(logits, top_k, capacity)

    # Switch-style load-balance auxiliary loss.
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = dispatch.sum((0, 2)) / jnp.maximum(dispatch.sum(), 1.0)
    frac_probs = probs.mean(0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    compute = x.dtype
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(compute),
                           tokens)  # all-to-all (token -> expert shards)
    expert_in = constrain(expert_in, ("expert", None, None))
    gate = jnp.einsum("ecd,edm->ecm", expert_in,
                      params["w_gate"].astype(compute))
    up = jnp.einsum("ecd,edm->ecm", expert_in,
                    params["w_up"].astype(compute))
    act = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("ecm,emd->ecd", act,
                            params["w_down"].astype(compute))
    expert_out = constrain(expert_out, ("expert", None, None))
    out = jnp.einsum("tec,ecd->td", combine.astype(compute), expert_out)
    return out.reshape(b, s, d), aux.astype(jnp.float32)


def init_moe_params(key: jax.Array, dim: int, mlp_dim: int,
                    num_experts: int, dtype=jnp.float32) -> Dict[str, Any]:
    import math

    k1, k2, k3, k4 = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    return {
        "router": normal(k1, (dim, num_experts), dim),
        "w_gate": normal(k2, (num_experts, dim, mlp_dim), dim),
        "w_up": normal(k3, (num_experts, dim, mlp_dim), dim),
        "w_down": normal(k4, (num_experts, mlp_dim, dim), mlp_dim),
    }


def moe_param_axes() -> Dict[str, Any]:
    return {
        "router": ("embed", "expert_dim"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


# ------------------------------------------------- dropless, held experts
#
# The serving form (``models/deepseek_decode.py``): the router is data,
# the layer is told which experts it holds, routes over all of them and
# computes its own experts' part of the result. No capacity, so no token
# is dropped; what the absent experts would add is left out (the chips
# that hold them add it in a deployment), and no code stands in for them.


@dataclasses.dataclass(frozen=True)
class Router:
    """How tokens choose experts, by a score of their logits: their
    softmax (DeepSeek-V2) or, under ``score="sigmoid"``, each logit's own
    sigmoid (``noaux_tc``: DeepSeek-V3, MiMo-V2).
    ``experts`` is the router's width (all experts of the layer, held here
    or not). With ``groups`` > 1 the
    experts form that many equal groups, each scored by its best expert,
    and only the ``top_groups`` best groups may be chosen from
    (DeepSeek-V2's ``group_limited_greedy``)."""

    experts: int
    top_k: int
    groups: int = 1
    top_groups: int = 1
    renormalise: bool = False     # weights of the chosen sum to 1
    scale: float = 1.0            # times this (``routed_scaling_factor``)
    score: str = "softmax"        # or "sigmoid"


def route(logits: jax.Array, router: Router,
          bias: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """``logits`` (T, experts) float32 -> the chosen experts ``(T, top_k)``
    int32 and their weights ``(T, top_k)`` float32. ``bias`` (experts,)
    is the layer's selection bias (``e_score_correction_bias``): data, as
    the router's matrix is; it is added to the scores for the CHOICE
    only, and the weights are the scores themselves."""
    r = router
    logits = logits.astype(jnp.float32)
    if r.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif r.score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score {r.score!r}")
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    if r.groups > 1:
        if bias is not None:
            raise ValueError("a selection bias with expert groups (a "
                             "group's score is then its two best) is not "
                             "implemented")
        t = scores.shape[0]
        best = scores.reshape(t, r.groups, -1).max(-1)        # (T, G)
        _, kept = jax.lax.top_k(best, r.top_groups)
        open_ = jnp.zeros((t, r.groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        choose = jnp.where(
            jnp.repeat(open_, r.experts // r.groups, axis=1), scores, 0.0)
    weights, idx = jax.lax.top_k(choose, r.top_k)
    if bias is not None:
        weights = jnp.take_along_axis(scores, idx, axis=-1)
    if r.renormalise:
        weights = weights / jnp.maximum(
            weights.sum(-1, keepdims=True), 1e-20)
    return idx.astype(jnp.int32), weights * r.scale


# Rows beyond what a balanced router gives a held expert, and tiles beyond
# one a held expert, before a second pass: a quarter more.
_SLACK = 1.25
_ROW_TILES = (128, 256, 512)
# A program that takes the held pairs' way carries three more kernels a
# scan segment: ~1 s of a warm set-up and ~5 s of a cold one a program (PR
# 50, command-a-plus: 12 such programs read +11 and +57 s), and an engine
# warms one for every chunk bucket and block-table width. So only
# chunk-sized calls take it: a 2,048-token chunk has 12,288-16,384 pairs in
# the three served models, a prompt's last piece at most this many.
_SMALL_CALL_PAIRS = 8192
# Where a chip holds many SMALL experts a small call does not ride on the
# read of their weights: ``ragged_dot`` tiles 512 rows a group whatever it
# holds, and a decode step of 2,112 pairs over 128 held experts of 5.5 M
# parameters (Nemotron-H: 4.1 pairs an expert) took 4.74 ms that way
# against 2.36 over tiles of 64 rows, the weights' read being 1.72
# (``microbench_moe.py``, PR 57). From this many held experts and this many
# pairs up every call takes the held pairs' way, and its tile may be this
# small. The other served models hold 16-40 experts.
_MANY_HELD = 64
_STEP_CALL_PAIRS = 1024
_STEP_TILE = 64


def held_rows(pairs: int, held: int, width: int
              ) -> Optional[Tuple[int, int]]:
    """``(cap, tile)``: how many rows a pass of the held pairs takes and the
    row tile of its grouped matmuls, for a call of ``pairs`` (token, expert)
    pairs routed over ``width`` experts of which ``held`` are here. The tile
    is the power of two that holds a balanced expert's rows and ``_SLACK``
    more (160 -> 256 in command-a-plus, 96 and 80 -> 128 in deepseek-v2 and
    mimo), each expert's rows start on a tile of their own, and a pass has a
    tile a held expert and ``_SLACK`` more. ``None`` where that is no fewer
    rows than the call has, or the call is small (``_SMALL_CALL_PAIRS``): a
    decode step's 192-256 pairs ride on the read of the held experts'
    weights, and keep every pair; but a small call of a thousand pairs over
    many held experts (``_MANY_HELD``) gets tiles of ``_STEP_TILE`` rows.
    A function of shapes alone."""
    many = held >= _MANY_HELD and pairs >= _STEP_CALL_PAIRS
    if pairs <= _SMALL_CALL_PAIRS and not many:
        return None
    want = pairs * _SLACK / width
    tiles = ((_STEP_TILE,) if many else ()) + _ROW_TILES
    tile = next((m for m in tiles if m >= want), tiles[-1])
    cap = math.ceil(held * _SLACK) * math.ceil(want / tile) * tile
    return (cap, tile) if many or cap < pairs else None


def held_experts_ffn(x: jax.Array, idx: jax.Array, weights: jax.Array,
                     experts: Dict[str, jax.Array],
                     held: Tuple[int, int],
                     keep: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None,
                     router: Optional[Router] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The part of a routed layer that the held experts give.

    ``x`` (T, D); ``idx``/``weights`` (T, k) from ``route``; ``experts``
    holds ``w_gate``/``w_up`` (H, D, M) and ``w_down`` (H, M, D) of the
    ``H = held[1]`` experts ``held[0] .. held[0] + H - 1``; ``keep`` (T,)
    bool leaves a token's pairs out (padding, a slot that does not step).
    The expert's FORM is read off its leaves: three make a SwiGLU,
    ``(silu(x w_gate) * (x w_up)) w_down``; two, ``w_up`` and ``w_down``
    alone, the squared ReLU ``relu(x w_up)^2 w_down`` (Nemotron-H).
    Returns ``(y (T, D), group_sizes (H,) int32)``: the sum over a token's
    HELD experts of weight x expert(x), and how many pairs each held
    expert computed.

    The experts' leaves may be stacked over layers, ``(L, H, ...)``, with
    ``layer`` (a traced index) naming the one to use: the matmuls then run
    over all ``L * H`` groups, the other layers' groups empty. A layer loop
    can so hand the stack in whole; a layer sliced out of it to feed the
    matmul's kernel is a copy (1.9 GB a layer at DeepSeek-V2's served
    size).

    ``router`` is the ``Router`` that made ``idx``: its width says what
    share of the ``T * k`` pairs a chip that holds ``H`` experts owns. The
    work of a call follows that share (``held_rows``): a chunk's thousands
    of pairs are cut to the held ones before a row is gathered, multiplied
    or brought back (``_held_pairs``); a decode step's few pairs, and any
    call without a ``router``, keep every pair (``_all_pairs``). Either
    way every held pair is computed, whatever the load looks like."""
    plan = None if router is None else held_rows(
        idx.shape[0] * idx.shape[1], held[1], router.experts)
    if plan is None:
        return _all_pairs(x, idx, weights, experts, held, keep, layer)
    with jax.named_scope(f"held_rows_{plan[0]}"):
        return _held_pairs(x, idx, weights, experts, held, keep, layer,
                           *plan, matmuls=_tiled if _gated(experts)
                           else _tiled_relu2)


def _held_groups(idx, held, keep):
    """``(mine (T, k) bool, group (T * k,) int32)``: which pairs are a held
    expert's and a kept token's, and each pair's held expert, ``held[1]``
    for every other pair."""
    first, count = held
    local = idx - first
    mine = (local >= 0) & (local < count)
    if keep is not None:
        mine &= keep[:, None]
    return mine, jnp.where(mine, local, count).reshape(-1)


def _gated(experts) -> bool:
    """Whether the experts are SwiGLUs (three leaves) or squared ReLUs
    (``w_up`` and ``w_down`` alone)."""
    return "w_gate" in experts


def _as_groups(experts, count):
    """A stack's ``(L, H, ...)`` leaves seen as ``L * H`` groups."""
    n_layers = experts["w_down"].shape[0]
    return {name: w.reshape((n_layers * count,) + w.shape[2:])
            for name, w in experts.items()}


def _stacked(experts, sizes, layer, count):
    """The experts' leaves and a call's group sizes as ``ragged_dot`` takes
    them: as they are, or a stack's leaves as groups of which only
    ``layer``'s have rows."""
    if layer is None:
        return experts, sizes
    stack = _as_groups(experts, count)
    return stack, jax.lax.dynamic_update_slice(
        jnp.zeros((stack["w_down"].shape[0],), jnp.int32), sizes,
        (layer * count,))


def _all_pairs(x, idx, weights, experts, held, keep, layer):
    """Every pair of the call in one sort: the (token, expert) pairs are
    sorted by expert, the pairs of absent experts last, and the expert's
    matmuls are ragged over the groups (``jax.lax.ragged_dot``). The rows
    past the last group (the absent experts' pairs) belong to no group, and
    what the chip's kernel leaves there is not defined: they are SELECTED
    away below, never multiplied by a zero weight (on a TPU v5e one run in
    eleven met a NaN there, PR 36)."""
    t, k = idx.shape
    count = held[1]
    mine, group = _held_groups(idx, held, keep)
    order = jnp.argsort(group, stable=True)
    token = order // k
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(jnp.int32)
    xs = x[token]                                              # (T * k, D)
    experts, groups = _stacked(experts, sizes, layer, count)
    if _gated(experts):
        gate = jax.lax.ragged_dot(xs, experts["w_gate"], groups)
        up = jax.lax.ragged_dot(xs, experts["w_up"], groups)
        hidden = jax.nn.silu(gate) * up
    else:
        hidden = jnp.square(jax.nn.relu(
            jax.lax.ragged_dot(xs, experts["w_up"], groups)))
    out = jax.lax.ragged_dot(hidden, experts["w_down"], groups)
    # Back in the tokens' order by a gather (the inverse permutation),
    # then a token's k pairs are added up in float32 under its weights.
    back = out[jnp.argsort(order)].reshape(t, k, -1).astype(jnp.float32)
    y = jnp.where(mine[..., None], back * weights[..., None], 0.0).sum(1)
    return y.astype(x.dtype), sizes


def _tiled(xs, stack, tile_group, live_tiles, tile):
    """The three matmuls over tiles that belong to one expert each."""
    hidden = grouped_matmul.grouped_swiglu(
        xs, stack["w_gate"], stack["w_up"], tile_group, live_tiles, tile)
    return grouped_matmul.grouped_matmul(
        hidden, stack["w_down"], tile_group, live_tiles, tile)


def _tiled_relu2(xs, stack, tile_group, live_tiles, tile):
    """The two matmuls of a squared-ReLU expert over the same tiles, the
    square between them XLA's (a dead tile's rows hold anything, before and
    after it; ``add_rows`` never adds them)."""
    up = grouped_matmul.grouped_matmul(xs, stack["w_up"], tile_group,
                                       live_tiles, tile)
    return grouped_matmul.grouped_matmul(
        jnp.square(jax.nn.relu(up)), stack["w_down"], tile_group,
        live_tiles, tile)


def _held_pairs(x, idx, weights, experts, held, keep, layer, cap, tile,
                matmuls=_tiled):
    """The held pairs only, ``cap`` rows a pass in tiles of ``tile``. The
    pairs are ranked by expert, the absent experts' last; each held
    expert's rows then start on a row tile of their own (the last one's
    rest is padding), so that a tile's matmuls read one expert's matrices.
    Pass ``p`` takes the tiles ``[p x cap / tile, (p + 1) x cap / tile)`` of
    that layout: it gathers their tokens' rows, runs the gate, up and down
    projections (``ops/grouped_matmul.py``) and adds each live row, under
    its pair's weight, into its token's float32 sum. A balanced call is one
    pass; when one expert takes every token the body runs again until no
    tile is left, so nothing is dropped and one body is compiled. Padding
    rows are computed from some pair's token and selected away, never
    multiplied by a zero."""
    t, k = idx.shape
    n, count = t * k, held[1]
    _, group = _held_groups(idx, held, keep)
    # A comparison a held expert, summed (``bincount`` is a scatter of
    # ones, which the chip walks).
    sizes = jnp.sum(group[:, None] == jnp.arange(count, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    # One key a pair, its expert then its place in the call: one operand
    # to sort, and the held pairs come first, by expert.
    ranked = jnp.sort(group * n + jnp.arange(n, dtype=jnp.int32)) % n
    tiles_of = -(-sizes // tile)
    tile_ends = jnp.cumsum(tiles_of)
    tile_starts, all_tiles = tile_ends - tiles_of, tile_ends[-1]
    stack, base = experts, 0
    if layer is not None:
        stack, base = _as_groups(experts, count), layer * count
    flat_weights = weights.reshape(-1)
    per_pass = cap // tile

    def one_pass(carry):
        p, y = carry
        tile_id = p * per_pass + jnp.arange(per_pass, dtype=jnp.int32)
        owner = jnp.minimum(
            jnp.searchsorted(tile_ends, tile_id, side="right"), count - 1)
        first_row = (tile_id - tile_starts[owner]) * tile
        tile_rows = jnp.where(tile_id < all_tiles,
                              jnp.clip(sizes[owner] - first_row, 0, tile), 0)
        rank = (starts[owner] + first_row)[:, None] \
            + jnp.arange(tile, dtype=jnp.int32)
        pair = ranked[jnp.minimum(rank, n - 1)].reshape(-1)    # (cap,)
        token = pair // k
        live_tiles = jnp.clip(all_tiles - p * per_pass, 0, per_pass)
        out = matmuls(x[token], stack, base + owner, live_tiles, tile)
        return p + 1, y + grouped_matmul.add_rows(
            out, flat_weights[pair], token, tile_rows, live_tiles, t, tile)

    _, y = jax.lax.while_loop(
        lambda carry: carry[0] * per_pass < all_tiles, one_pass,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32)))
    return y.astype(x.dtype), sizes
