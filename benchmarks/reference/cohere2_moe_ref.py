"""Plain reference of Command A+ as the ``command-a-plus`` cell serves it:
float32 ``jax.numpy``, matmuls at the highest precision, no cache, no
pages, no kernels, no batching, one sequence at a time. Written from the
published ``config.json`` (``model_type`` ``cohere2_moe``), layer ``l`` of
which is a PARALLEL block on ``h = LN(x)``, ``LN(v) = (v - mean(v)) /
sqrt(var(v) + 1e-5) * g_l`` (no bias):

* attention of the kind ``layer_types[l]`` names: ``q = h W_Q`` [128 x
  128], ``k = h W_K``, ``v = h W_V`` [8 x 128], no biases, no q/k norm. A
  ``sliding_attention`` layer turns the pairs ``(2i, 2i + 1)`` of all 128
  numbers of every head of ``q`` and ``k`` by the position (``rope_gptj``),
  base 50,000, and query ``i`` sees the keys ``j`` with ``0 <= i - j <
  4096``; a ``full_attention`` layer has NO position term and is causal
  over everything. Scores ``q . k / sqrt(128)``, softmax, 16 query heads a
  key head; ``a = concat(heads) W_O``.
* feed-forward on the same ``h``: ``s = sigmoid(h W_R)`` (128 scores, no
  selection bias), the 8 largest chosen, weights ``s`` of the chosen over
  their sum; ``f = sum_e w_e SwiGLU_e(h) + (1 / 4) sum_j SwiGLU_shared_j(h)``
  (``shared_expert_combination_strategy`` ``average``).
* ``x <- x + a + f``. After the last layer ``LN`` and ``logit_scale * (h
  E^T)`` with the embedding ``E``.

Departures, all noted in the configuration file: the chip's SHARE (the
routed sum runs over the experts held here, so a token none of whose 8
experts is held gets the shared experts' part only; the vocabulary is the
rows held here); the vision tower is not part of the language model's
forward.

The routing, the masks, the rotation and the LayerNorm are this file's
own. Nothing of ``ray_tpu.models`` or ``ray_tpu.ops`` is used; only the
LAYOUT of the replica's weight tree is (consecutive layers of one kind are
stacked as a segment, ``_layers`` below; ``wqkv`` holds queries, keys and
values side by side, ``shared`` the shared experts side by side, which
this file cuts apart again and runs ONE BY ONE). The weights are upcast a
layer and an expert at a time, so the reference fits beside a served model
that fills the chip; attention goes one key head's 16 query heads and one
block of queries at a time for the same reason (a block's scores are 16 x
256 x S float32: 0.16 GB at the check's longest prompt), and the head is
taken at the rows that are asked for."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def _w(a, bits: Optional[int]):
    """A weight slice in float32; under ``bits`` rounded to that many
    bits, symmetric, one scale per index of its last axis (the control of
    ``correct``, ``benchmarks/control.py``)."""
    a = a.astype(jnp.float32)
    if bits is None:
        return a
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(a), axis=tuple(range(a.ndim - 1)),
                    keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(a / scale) * scale


def _layer_norm(x, scale, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


def _layers(cfg) -> List[Tuple[int, int, bool]]:
    """For each layer ``(segment, index in it, window?)``: the weight tree
    stacks a run of layers of one kind."""
    out: List[Tuple[int, int, bool]] = []
    seg, at = -1, 0
    for l in range(cfg.n_layers):
        window = cfg.layer_types[l] == "sliding_attention"
        if out and window == out[-1][2]:
            at += 1
        else:
            seg, at = seg + 1, 0
        out.append((seg, at, window))
    return out


def _rope(x, theta: float):
    """``x`` (S, heads, D): turn the pairs ``(2i, 2i + 1)`` of each head
    by the position on axis 0, in place (interleaved, as published)."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(seg, l, h, cfg, window: bool, bits):
    """The attention's half of layer ``l`` of segment ``seg`` on one
    sequence's norm ``h`` (S, E): ``concat(heads) W_O``, one key head and
    its 16 query heads at a time, their columns of the projection upcast
    as they are used."""
    s = h.shape[0]
    heads, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = heads // n_kv
    at = jnp.arange(s)
    qb = math.gcd(s, QUERY_BLOCK)
    wqkv, wo = seg["wqkv"][l], seg["wo"][l]              # (E, F), (H, D, E)

    def project(first, width):
        cols = jax.lax.dynamic_slice_in_dim(wqkv, first, width, 1)
        return (h @ _w(cols, bits)).reshape(s, width // d, d)

    def key_head(out, kh):
        """The queries that read key head ``kh`` add their share of
        ``W_O concat_h(P v)``."""
        mine = project(kh * group * d, group * d)               # (S, G, D)
        keys = project((heads + kh) * d, d)                     # (S, 1, D)
        vals = project((heads + n_kv + kh) * d, d)[:, 0]        # (S, D)
        if window:
            mine, keys = (_rope(mine, cfg.rope_theta),
                          _rope(keys, cfg.rope_theta))
        keys = keys[:, 0]

        def queries(q0):
            """One block of queries against every key, masked."""
            qs = jax.lax.dynamic_slice_in_dim(mine, q0, qb)     # (qb, G, D)
            a = jnp.einsum("qgd,kd->gqk", qs, keys) / math.sqrt(d)
            i = (q0 + jnp.arange(qb))[:, None]
            seen = at[None, :] <= i
            if window:
                seen &= i - at[None, :] < cfg.window
            a = jnp.where(seen[None], a, -jnp.inf)
            p = jax.nn.softmax(a, axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vals)

        a = jax.lax.map(queries, jnp.arange(0, s, qb))
        a = a.reshape((s,) + a.shape[2:])                      # (S, G, D)
        w = _w(jax.lax.dynamic_slice_in_dim(wo, kh * group, group, 0), bits)
        return out + jnp.einsum("qgd,gde->qe", a, w), None

    out, _ = jax.lax.scan(key_head, jnp.zeros_like(h), jnp.arange(n_kv))
    return out


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _route(scores, cfg):
    """(S, experts) float32 sigmoid scores -> (S, experts) weights, zero
    for the experts a token did not choose: the ``top_k`` largest scores,
    each over their sum."""
    s = scores.shape[0]
    chosen = jnp.argsort(-scores, axis=-1)[:, :cfg.top_k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], chosen].set(w)


def _feed_forward(seg, l, h, cfg, bits):
    """The feed-forward's half of the layer on the norm ``h`` (S, E)."""
    weights = _route(jax.nn.sigmoid(h @ _w(seg["router"][l], bits)), cfg)
    first, count = cfg.experts_held or (0, cfg.n_routed_experts)
    ex, sh, m = seg["experts"], seg["shared"], cfg.mlp_dim

    def routed(e, y):
        # This chip's share: the experts it holds, one at a time.
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return y + w[:, None] * _swiglu(
            h, _w(ex["w_gate"][l, e], bits), _w(ex["w_up"][l, e], bits),
            _w(ex["w_down"][l, e], bits))

    y = jax.lax.fori_loop(0, count, routed, jnp.zeros_like(h))
    # The shared experts one by one (the tree holds them side by side,
    # and each is cut out before it is upcast), their outputs AVERAGED.
    def one(j):
        cols = slice(j * m, (j + 1) * m)
        return _swiglu(h, _w(sh["w_gate"][l][:, cols], bits),
                       _w(sh["w_up"][l][:, cols], bits),
                       _w(sh["w_down"][l][cols], bits))

    shared = sum(one(j) for j in range(cfg.n_shared_experts))
    return y + shared / cfg.n_shared_experts


@partial(jax.jit, static_argnames=("cfg", "window", "bits"))
def _layer(seg, l, x, cfg, window, bits):
    with jax.default_matmul_precision("highest"):
        h = _layer_norm(x, seg["norm"][l], cfg.norm_eps)
        return x + _attention(seg, l, h, cfg, window, bits) \
            + _feed_forward(seg, l, h, cfg, bits)


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _head(params, x, cfg, bits):
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, params["final_norm"], cfg.norm_eps)
        return cfg.logit_scale * (x @ _w(params["tok_embed"], bits).T)


def logits(params: Dict[str, Any], tokens, cfg,
           bits: Optional[int] = None, rows=None) -> jax.Array:
    """Token ids (S,) of ONE sequence -> float32 logits (S, V), causal;
    under ``rows`` the logits at those positions only."""
    x = params["tok_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if bits is not None:
        # The embedding's scale is per column over the whole table.
        x = _w(params["tok_embed"], bits)[jnp.asarray(tokens)]
    for seg, l, window in _layers(cfg):
        x = _layer(params["segments"][seg], l, x, cfg, window, bits)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(params, x, cfg, bits)


def _padded(rows: List[List[int]], longest: int) -> np.ndarray:
    """Right-padded to ``longest`` rounded up to whole query blocks (a
    few widths, so a few compiles over a process's seeds)."""
    width = -(-longest // QUERY_BLOCK) * QUERY_BLOCK
    out = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def served_token_margins(params, cfg, prompts: List[List[int]],
                         answers: List[List[int]]) -> List[float]:
    """For each served token, ``max(logits) - logits[token]`` at its
    position under the reference, teacher-forced on prompt + answer. Every
    sequence is padded on the right to one length (causal, so padding
    changes nothing before it) and goes through on its own."""
    rows = [list(p) + list(a) for p, a in zip(prompts, answers)]
    padded = _padded(rows, max(len(r) for r in rows))
    out = []
    for row, p, a in zip(padded, prompts, answers):
        at = np.arange(len(p) - 1, len(p) + len(a) - 1)
        lg = np.asarray(logits(params, row, cfg, rows=at))
        out += [float(lg[j].max() - lg[j, tok]) for j, tok in enumerate(a)]
    return out


def cut_prompt_margins(params, cfg, prompts: List[List[int]], n: int,
                       bits: int) -> List[float]:
    """The control's tokens and their margins
    (``deepseek_v2_ref.cut_prompt_margins``): with its weights rounded to
    ``bits`` bits this reference answers ONE token after each of the last
    ``n`` cuts of every prompt, one causal forward giving all ``n``;
    returned is each such token's margin under the UNROUNDED reference at
    the same position."""
    padded = _padded(prompts, max(len(p) for p in prompts))
    out = []
    for row, p in zip(padded, prompts):
        at = np.arange(len(p) - n, len(p))
        said = np.asarray(logits(params, row, cfg, bits, rows=at)).argmax(-1)
        lg = np.asarray(logits(params, row, cfg, rows=at))
        out += [float(lg[j].max() - lg[j, tok]) for j, tok in enumerate(said)]
    return out
