"""Plain reference of Phi-4-mini-flash-reasoning as the ``phi-4-mini-flash``
cell serves it: float32 ``jax.numpy``, matmuls at the highest precision, a
``lax.scan`` over time for the state-space layers, full masks for
attention, EVERY layer at EVERY position, no cache, no pages, no state
carried, no kernels, no batching, one sequence at a time. Written from the
published ``config.json`` (``model_type`` ``phi4flash``) and the papers its
modelling code follows (SambaY arXiv:2507.06607, differential attention
arXiv:2410.05258, Mamba arXiv:2312.00752). With ``half = layers / 2``, layer
``l`` is ``h += mixer(LN1(h)); h += W2(silu(g) * u)``, ``[g | u] = W1
LN2(h)``, and its mixer

* ``l <= half``, even: Mamba. ``[x | z] = W_in u``; ``x = silu(conv4(x) +
  b)``, causal and depthwise; ``[dt_r | B | C] = W_x x``; ``dt =
  softplus(W_dt dt_r + b_dt)``; ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t)
  B_t^T`` with ``A = -exp(A_log)``; ``y_t = s_t C_t + D x_t``; out ``=
  W_out(y silu(z))``. Layer ``half`` hands ``m = y`` on.
* ``l < half``, odd, and ``l = half + 1``: differential attention, causal
  (the odd layers below ``half`` over the last ``sliding_window`` keys).
  ``q`` as pairs ``i`` of two heads, ``k`` and ``v`` likewise, pair ``i``
  reads key-value pair ``i // g``; ``V = [v1 | v2]``; ``a_s = softmax(q_s
  k_s^T / sqrt(d)) V``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; ``o = RMSNorm(a_1 - lam a_2) (1 -
  lam0)``; out ``= W_o o + b_o``.
* ``l > half + 1``, odd: the same with its own queries, ``lam`` vectors,
  sub-norm and ``W_o`` over the keys and values of layer ``half + 1``.
* ``l > half + 1``, even: ``W_out(silu(W_in u) * m)``.

No positional term anywhere; the head is the embedding, transposed.

Nothing of ``ray_tpu.models`` or ``ray_tpu.ops`` is used; only the LAYOUT
of the replica's weight tree is (layers of one mixer stacked, ``A_log`` as
``(d_state, d_inner)``, a fused ``[q | k | v]``). The weights are upcast a
layer at a time and the head a block of the vocabulary at a time, at the
rows that are asked for, so the reference fits beside a served model that
fills the chip."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 256
HEAD_BLOCK = 16384


def _scale(a, bits: int):
    """One scale per index of ``a``'s last axis, for ``bits`` bits."""
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(a).astype(jnp.float32),
                    axis=tuple(range(a.ndim - 1)), keepdims=True) / top
    return jnp.where(scale == 0, 1.0, scale)


def _w(a, bits: Optional[int], scale=None):
    """A weight slice in float32; under ``bits`` rounded to that many
    bits, symmetric (the control of ``correct``, ``benchmarks/control.py``)."""
    a = a.astype(jnp.float32)
    if bits is None:
        return a
    scale = _scale(a, bits) if scale is None else scale
    return jnp.round(a / scale) * scale


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _mlp(p, x, cfg, bits):
    h = _ln(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
    gu = h @ _w(p["w1"], bits)
    f = cfg.mlp_dim
    return x + (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ _w(p["w2"], bits)


def _at(tree, l):
    """Layer ``l`` of a stacked group of leaves (the group itself where
    ``l`` is None)."""
    if l is None:
        return tree
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, False)
            for k, v in tree.items()}


@partial(jax.jit, static_argnames=("cfg", "bits", "stacked"))
def _mamba_layer(tree, l, x, cfg, bits, stacked):
    """One sequence ``x`` (S, E) -> ``(x, y)``, ``y`` (S, Di) the scan's
    output before the gate."""
    with jax.default_matmul_precision("highest"):
        p = _at(tree, l if stacked else None)
        s, di, n, r = x.shape[0], cfg.d_inner, cfg.d_state, cfg.dt_rank
        u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        xz = u @ _w(p["in_proj"], bits)
        xs, z = xz[:, :di], xz[:, di:]
        taps = _w(p["conv_w"], bits)                          # (K, Di)
        k = taps.shape[0]
        padded = jnp.concatenate([jnp.zeros((k - 1, di)), xs])
        xc = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(k))
                         + p["conv_b"])
        proj = xc @ _w(p["x_proj"], bits)
        dt = jax.nn.softplus(proj[:, :r] @ _w(p["dt_proj"], bits)
                             + p["dt_bias"])
        a = -jnp.exp(p["A_log"])                              # (N, Di)

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = jnp.exp(dt_t[None, :] * a) * state \
                + (dt_t * x_t)[None, :] * b_t[:, None]
            return state, (state * c_t[:, None]).sum(0) + p["D"] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((n, di)),
                            (xc, dt, proj[:, r:r + n], proj[:, r + n:]))
        x = x + (y * jax.nn.silu(z)) @ _w(p["out_proj"], bits)
        return _mlp(p, x, cfg, bits), y


def _differential(p, q, k, v, lam0, cfg, bits, window: Optional[int]):
    """``q`` (S, E), ``k`` and ``v`` (S, KV x D) of one sequence -> the
    block's output (S, E)."""
    s, d = q.shape[0], cfg.head_dim
    g = cfg.q_pairs // cfg.kv_pairs
    q = q.reshape(s, cfg.kv_pairs, g, 2, d)
    k = k.reshape(s, cfg.kv_pairs, 2, d)
    v = v.reshape(s, cfg.kv_pairs, 2 * d)
    at = jnp.arange(s)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen &= at[:, None] - at[None, :] < window
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0

    def pair(_, j):
        qs = jax.lax.dynamic_index_in_dim(q, j, 1, False)    # (S, g, 2, D)
        ks = jax.lax.dynamic_index_in_dim(k, j, 1, False)    # (S, 2, D)
        vs = jax.lax.dynamic_index_in_dim(v, j, 1, False)    # (S, 2 D)
        a = jnp.einsum("qghd,khd->ghqk", qs, ks) / math.sqrt(d)
        a = jnp.where(seen[None, None], a, -jnp.inf)
        pr = jax.nn.softmax(a, axis=-1)
        o = jnp.einsum("ghqk,kd->qghd", pr, vs)              # (S, g, 2, 2D)
        o = o[:, :, 0] - lam * o[:, :, 1]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * p["subln"]
        return None, o * (1.0 - lam0)

    _, o = jax.lax.scan(pair, None, jnp.arange(cfg.kv_pairs))
    o = o.transpose(1, 0, 2, 3).reshape(s, cfg.dim)          # pairs in order
    return o @ _w(p["o_proj"], bits) + p["o_bias"]


@partial(jax.jit, static_argnames=("cfg", "bits", "stacked", "window"))
def _attention_layer(tree, l, x, lam0, cfg, bits, stacked, window):
    """Self-attention over one sequence; returns ``(x, k, v)``."""
    with jax.default_matmul_precision("highest"):
        p = _at(tree, l if stacked else None)
        e, kv = cfg.dim, cfg.kv_width
        u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        qkv = u @ _w(p["wqkv"], bits) + p["bqkv"]
        k, v = qkv[:, e:e + kv], qkv[:, e + kv:]
        x = x + _differential(p, qkv[:, :e], k, v, lam0, cfg, bits, window)
        return _mlp(p, x, cfg, bits), k, v


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _cross_pair(gmu, cross, l, x, m, k, v, lam0, cfg, bits):
    """A gated memory unit layer and the cross-attention layer behind it."""
    with jax.default_matmul_precision("highest"):
        p = _at(gmu, l)
        u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        x = x + (jax.nn.silu(u @ _w(p["in_proj"], bits)) * m) \
            @ _w(p["out_proj"], bits)
        x = _mlp(p, x, cfg, bits)
        p = _at(cross, l)
        u = _ln(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        q = u @ _w(p["wqkv"], bits) + p["bqkv"]
        x = x + _differential(p, q, k, v, lam0, cfg, bits, None)
        return _mlp(p, x, cfg, bits)


@partial(jax.jit, static_argnames=("cfg",))
def _final_norm(params, x, cfg):
    return _ln(x, params["final_ln_w"], params["final_ln_b"], cfg.norm_eps)


@partial(jax.jit, static_argnames=("bits",))
def _head_block(x, rows, bits, scale):
    with jax.default_matmul_precision("highest"):
        return x @ _w(rows, bits, scale).T


def _lam0(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def logits(params: Dict[str, Any], tokens, cfg,
           bits: Optional[int] = None, rows=None) -> jax.Array:
    """Token ids (S,) of ONE sequence -> float32 logits (S, V), causal;
    under ``rows`` the logits at those positions only."""
    embed = params["tok_embed"]
    # The embedding's scale is per column over the whole table.
    scale = None if bits is None else _scale(embed, bits)
    x = _w(embed[jnp.asarray(tokens)], bits, scale)
    half = cfg.n_layers // 2
    for i in range(half // 2):
        x, _ = _mamba_layer(params["mamba"], i, x, cfg, bits, True)
        x, _, _ = _attention_layer(params["window"], i, x, _lam0(2 * i + 1),
                                   cfg, bits, True, cfg.window)
    x, m = _mamba_layer(params["mamba_mid"], 0, x, cfg, bits, False)
    x, k, v = _attention_layer(params["full"], 0, x, _lam0(half + 1), cfg,
                               bits, False, None)
    for i in range(half // 2 - 1):
        x = _cross_pair(params["gmu"], params["cross"], i, x, m, k, v,
                        _lam0(half + 3 + 2 * i), cfg, bits)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _final_norm(params, x, cfg)
    return jnp.concatenate(
        [_head_block(x, embed[i:i + HEAD_BLOCK], bits, scale)
         for i in range(0, embed.shape[0], HEAD_BLOCK)], axis=-1)


def _padded(rows: List[List[int]], longest: int) -> np.ndarray:
    """Right-padded to ``longest`` rounded up to ``PAD_TO`` (a few widths,
    so a few compiles over a process's seeds)."""
    width = -(-longest // PAD_TO) * PAD_TO
    out = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def served_token_margins(params, cfg, prompts: List[List[int]],
                         answers: List[List[int]]) -> List[float]:
    """For each served token, ``max(logits) - logits[token]`` at its
    position under the reference, teacher-forced on prompt + answer. Every
    sequence is padded on the right to one length (every layer is causal,
    so padding changes nothing before it) and goes through on its own."""
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
