"""Plain reference of MiMo-V2 as the ``mimo-v2.5`` cell serves it: float32
``jax.numpy``, matmuls at the highest precision, no cache, no pages, no
kernels, no batching, one sequence at a time. Written from the published
``config.json`` (``model_type`` ``mimo_v2``), layer ``l`` of which is

* attention of the kind ``hybrid_layer_pattern[l]`` names: ``h =
  RMSNorm(x)``; one fused projection gives ``q`` [64 x 192], ``k`` [n_kv x
  192], ``v`` [n_kv x 128], n_kv 4 (full, 0) or 8 (window, 1); rotary on
  the first ``int(192 x 0.334)`` = 64 of each 192, pairs ``(i, i + 32)``,
  base ``rope_theta`` (full) or ``swa_rope_theta`` (window); ``v <- 0.707
  v``; scores ``q . k / sqrt(192)``, causal, a window layer keeping the keys
  ``j`` with ``0 <= i - j < 128``; a window layer's softmax has one learned
  logit ``s_h`` a head in its denominator that takes no value, ``p_ij =
  exp(a_ij - m) / (exp(s_h - m) + sum_j exp(a_ij - m))``; ``W_O``.
* feed-forward as ``moe_layer_freq[l]`` names: SwiGLU of 16,384 (0), or
  (1) ``g = sigmoid(RMSNorm(x) W_G)``, the top 8 of ``g + b`` chosen,
  weights ``g`` of the chosen over their sum, ``y = sum_e w_e SwiGLU_e``; no
  shared expert.

Departures, all noted in the configuration file: the chip's SHARE (the sum
runs over the experts held here, so a token none of whose 8 experts is
held gets zero from the layer; the vocabulary is the rows held here); the
towers and the multi-token-prediction layers are not part of the language
model's forward.

The routing, the mask and the sink are this file's own. Nothing of
``ray_tpu.models`` or ``ray_tpu.ops`` is used; only the LAYOUT of the
replica's weight tree is (consecutive layers of one kind and one
feed-forward are stacked as a segment, ``_layers`` below). The weights are
upcast a layer and an expert at a time, so the reference fits beside a
served model that fills the chip; attention goes one key head's queries
and one block of queries at a time for the same reason (a block's scores
are 16 x 512 x S float32: 0.2 GB at the check's longest prompt), and the
head is taken at the rows that are asked for."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layers(cfg) -> List[Tuple[int, int, bool, bool]]:
    """For each layer ``(segment, index in it, window?, experts?)``: the
    weight tree stacks a run of layers of one kind and one feed-forward."""
    out: List[Tuple[int, int, bool, bool]] = []
    seg, at = -1, 0
    for l in range(cfg.n_layers):
        mine = (bool(cfg.layer_pattern[l]), bool(cfg.moe_pattern[l]))
        if out and mine == out[-1][2:]:
            at += 1
        else:
            seg, at = seg + 1, 0
        out.append((seg, at) + mine)
    return out


def _rope(x, theta: float, rotary: int):
    """``x`` (S, heads, D): turn the pairs ``(i, i + rotary / 2)`` of the
    first ``rotary`` numbers of each head by the position on axis 0."""
    s, half = x.shape[0], rotary // 2
    inv = 1.0 / (theta ** (np.arange(0, rotary, 2, dtype=np.float32)
                           / rotary))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], -1)


def _attention(seg, l, x, cfg, window: bool, bits):
    """Layer ``l`` of segment ``seg`` on one sequence ``x`` (S, E)."""
    s = x.shape[0]
    n_kv = cfg.swa_n_kv_heads if window else cfg.n_kv_heads
    theta = cfg.swa_rope_theta if window else cfg.rope_theta
    heads, d, dv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    h = _rms_norm(x, seg["attn_norm"][l], cfg.norm_eps)
    qkv = h @ _w(seg["wqkv"][l], bits)
    q = _rope(qkv[:, :heads * d].reshape(s, heads, d), theta,
              cfg.rotary_dim)
    k = _rope(qkv[:, heads * d:(heads + n_kv) * d].reshape(s, n_kv, d),
              theta, cfg.rotary_dim)
    v = qkv[:, (heads + n_kv) * d:].reshape(s, n_kv, dv) * cfg.value_scale
    sinks = seg["sink"][l] if "sink" in seg else None
    group = heads // n_kv
    at = jnp.arange(s)
    qb = math.gcd(s, QUERY_BLOCK)
    wo = _w(seg["wo"][l], bits)                          # (H, Dv, E)

    def key_head(out, kh):
        """The queries that read key head ``kh`` add their share of
        ``W_O concat_h(P v)``."""
        mine = jax.lax.dynamic_slice_in_dim(q, kh * group, group, 1)
        keys = jax.lax.dynamic_index_in_dim(k, kh, 1, False)   # (S, D)
        vals = jax.lax.dynamic_index_in_dim(v, kh, 1, False)   # (S, Dv)

        def queries(q0):
            """One block of queries against every key, masked."""
            qs = jax.lax.dynamic_slice_in_dim(mine, q0, qb)     # (qb, G, D)
            a = jnp.einsum("qgd,kd->gqk", qs, keys) / math.sqrt(d)
            i = (q0 + jnp.arange(qb))[:, None]
            seen = at[None, :] <= i
            if window:
                seen &= i - at[None, :] < cfg.window
            a = jnp.where(seen[None], a, -jnp.inf)
            m = a.max(-1, keepdims=True)
            extra = 0.0
            if sinks is not None:
                sk = jax.lax.dynamic_slice_in_dim(
                    sinks, kh * group, group)[:, None, None]
                m = jnp.maximum(m, sk)
                extra = jnp.exp(sk - m)
            e = jnp.exp(a - m)
            p = e / (extra + e.sum(-1, keepdims=True))
            return jnp.einsum("gqk,kd->qgd", p, vals)

        a = jax.lax.map(queries, jnp.arange(0, s, qb))
        a = a.reshape((s,) + a.shape[2:])                      # (S, G, Dv)
        w = jax.lax.dynamic_slice_in_dim(wo, kh * group, group, 0)
        return out + jnp.einsum("qgd,gde->qe", a, w), None

    out, _ = jax.lax.scan(key_head, x, jnp.arange(n_kv))
    return out


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _route(scores, bias, cfg):
    """(S, experts) float32 sigmoid scores -> (S, experts) weights, zero
    for the experts a token did not choose: the top ``top_k`` of ``scores +
    bias``, weighted by their scores over the scores' sum."""
    s = scores.shape[0]
    chosen = jnp.argsort(-(scores + bias[None, :]), axis=-1)[:, :cfg.top_k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], chosen].set(w)


@partial(jax.jit, static_argnames=("cfg", "window", "bits"))
def _dense_layer(seg, l, x, cfg, window, bits):
    with jax.default_matmul_precision("highest"):
        x = _attention(seg, l, x, cfg, window, bits)
        h = _rms_norm(x, seg["mlp_norm"][l], cfg.norm_eps)
        return x + _swiglu(h, _w(seg["w_gate"][l], bits),
                           _w(seg["w_up"][l], bits),
                           _w(seg["w_down"][l], bits))


@partial(jax.jit, static_argnames=("cfg", "window", "bits"))
def _moe_layer(seg, l, x, cfg, window, bits):
    with jax.default_matmul_precision("highest"):
        x = _attention(seg, l, x, cfg, window, bits)
        h = _rms_norm(x, seg["mlp_norm"][l], cfg.norm_eps)
        scores = jax.nn.sigmoid(h @ _w(seg["router"][l], bits))
        weights = _route(scores, seg["router_bias"][l], cfg)
        first, count = cfg.experts_held or (0, cfg.n_routed_experts)
        ex = seg["experts"]

        def one(e, y):
            # This chip's share: the experts it holds, one at a time.
            w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
            return y + w[:, None] * _swiglu(
                h, _w(ex["w_gate"][l, e], bits), _w(ex["w_up"][l, e], bits),
                _w(ex["w_down"][l, e], bits))

        return x + jax.lax.fori_loop(0, count, one, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _head(params, x, cfg, bits):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x @ _w(params["lm_head"], bits)


def logits(params: Dict[str, Any], tokens, cfg,
           bits: Optional[int] = None, rows=None) -> jax.Array:
    """Token ids (S,) of ONE sequence -> float32 logits (S, V), causal;
    under ``rows`` the logits at those positions only."""
    x = params["tok_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if bits is not None:
        # The embedding's scale is per column over the whole table.
        x = _w(params["tok_embed"], bits)[jnp.asarray(tokens)]
    for seg, l, window, experts in _layers(cfg):
        layer = _moe_layer if experts else _dense_layer
        x = layer(params["segments"][seg], l, x, cfg, window, bits)
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
