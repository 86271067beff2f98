"""Plain reference of Nemotron-H as the ``nemotron-3-super`` cell serves it:
float32 ``jax.numpy``, matmuls at the highest precision, no cache, no
pages, no state carried, no kernels, no batching, one sequence at a time.
Written from the published ``config.json`` (``model_type`` ``nemotron_h``),
arXiv:2405.21060 and the Nemotron-H report (arXiv:2504.03624). Layer ``l``
is ONE part on ``u = RMSNorm(x) * g_l`` (eps 1e-5), ``x <- x + part(u)``,
the part named by letter ``l`` of ``hybrid_override_pattern``:

* ``M``: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv(xBC) + b)``, a
  depthwise causal convolution over the last 4 positions (zeros before
  position 0); ``xBC = [x | B | C]``, 128 heads of 64, 8 groups of 128,
  head ``h`` reads group ``h // 16``; ``dt_h = softplus(dt_h + dt_bias_h)``
  (no clamp), ``A_h = -exp(A_log_h)``; BY THE RECURRENCE, a ``lax.scan``
  over time: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from ``S = 0``,
  ``y_t = S_t C_t + D_h x_t`` (the program computes by chunks of matmuls,
  so each formulation checks the other); ``y <- y * silu(z)``, then
  RMS-normalised inside each of the 8 groups of 1,024 channels, times a
  weight; ``part = y W_out``.
* ``*``: 32 query heads over 2 key-value heads of 128, scores ``q . k /
  sqrt(128)``, causal over everything, NO positional term;
  ``part = concat(heads) W_O``.
* ``E``: ``s = sigmoid(u W_r)`` (512 scores); the 22 largest ``s + bias``
  chosen, weights ``s`` of the chosen over their sum (+ 1e-20), x 5;
  ``l = u W_1`` (4,096 -> 1,024); ``f_e(l) = relu(l U_e)^2 D_e``;
  ``part = (sum_e w_e f_e(l)) W_2 + relu(u U_s)^2 D_s``.

After the last layer RMSNorm and the untied head.

Departures, all noted in the configuration file: the chip's SHARE (the
routed sum runs over the experts held here, so a token none of whose 22
experts is held gets the shared expert's part only; the vocabulary is the
rows held here); the multi-token prediction layer is not part of the
forward.

The routing, the masks, the convolution, the recurrence and the norms are
this file's own. Nothing of ``ray_tpu.models`` or ``ray_tpu.ops`` is used;
only the LAYOUT of the replica's weight tree is (a run of layers of one
letter is stacked as a segment, ``_layers`` below; ``in_proj`` holds ``z``,
``xBC`` and ``dt`` side by side, ``wkv`` keys and values). The weights are
upcast a block at a time (a Mamba-2 layer a group of 16 heads at a time,
which is also one group of the norm; the experts an expert at a time), so
the reference fits beside a served model that fills the chip; attention
goes one key head's 16 query heads and one block of queries at a time for
the same reason, and the head is taken at the rows that are asked for."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
_LETTER = {"M": "mamba", "*": "full", "E": "experts"}


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


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _layers(cfg) -> List[Tuple[int, int, str]]:
    """For each layer ``(segment, index in it, letter)``: the weight tree
    stacks a run of layers of one letter."""
    out: List[Tuple[int, int, str]] = []
    seg, at = -1, 0
    for letter in cfg.pattern[:cfg.n_layers]:
        if out and letter == out[-1][2]:
            at += 1
        else:
            seg, at = seg + 1, 0
        out.append((seg, at, letter))
    return out


def _cols(w, first, width, bits):
    return _w(jax.lax.dynamic_slice_in_dim(w, first, width, 1), bits)


def _mamba(seg, l, u, cfg, bits):
    """The Mamba-2 part on one sequence's norm ``u`` (S, E), one GROUP at a
    time: its 16 heads, its ``B`` and ``C``, its share of the gate, of the
    norm and of ``W_out``."""
    s = u.shape[0]
    heads, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
                      cfg.ssm_state)
    di, per, taps = heads * p, heads // g, cfg.d_conv
    width = per * p                                  # a group's channels
    w_in, w_out = seg["in_proj"][l], seg["out_proj"][l]
    conv_w = seg["conv_w"][l].astype(jnp.float32)    # (taps, conv_dim)
    conv_b = seg["conv_b"][l].astype(jnp.float32)
    A = -jnp.exp(seg["A_log"][l].astype(jnp.float32))
    D = seg["D"][l].astype(jnp.float32)
    dt_bias = seg["dt_bias"][l].astype(jnp.float32)
    gnorm = seg["gnorm"][l].astype(jnp.float32)

    def conv(first, count):
        """``silu(conv(u W_in[:, cols]) + b)`` for ``count`` channels of
        ``xBC`` from ``first``: zeros before position 0."""
        raw = u @ _cols(w_in, di + first, count, bits)
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, count), jnp.float32), raw])
        cw = jax.lax.dynamic_slice_in_dim(conv_w, first, count, 1)
        acc = jax.lax.dynamic_slice_in_dim(conv_b, first, count)[None]
        for k in range(taps):
            acc = acc + cw[k][None] * padded[k:k + s]
        return jax.nn.silu(acc)

    def group(out, j):
        x = conv(j * width, width).reshape(s, per, p)
        b = conv(di + j * n, n)                               # (S, N)
        c = conv(di + g * n + j * n, n)
        z = u @ _cols(w_in, j * width, width, bits)
        dt = jax.nn.softplus(
            u @ _cols(w_in, di + cfg.conv_dim + j * per, per, bits)
            + jax.lax.dynamic_slice_in_dim(dt_bias, j * per, per))
        a = jax.lax.dynamic_slice_in_dim(A, j * per, per)
        d = jax.lax.dynamic_slice_in_dim(D, j * per, per)

        def step(S, inp):
            x_t, b_t, c_t, dt_t = inp                  # (per, p), (n,), ..
            S = jnp.exp(dt_t * a)[:, None, None] * S \
                + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
            return S, jnp.sum(S * c_t[None, None, :], -1) \
                + d[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((per, p, n), jnp.float32),
                            (x, b, c, dt))
        y = y.reshape(s, width) * jax.nn.silu(z)
        y = _rms(y, jax.lax.dynamic_slice_in_dim(gnorm, j * width, width),
                 cfg.norm_eps)
        rows = jax.lax.dynamic_slice_in_dim(w_out, j * width, width, 0)
        return out + y @ _w(rows, bits), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(u), jnp.arange(g))
    return out


def _attention(seg, l, u, cfg, bits):
    """The attention part on one sequence's norm ``u`` (S, E): one key head
    and its query heads at a time, their columns of the projections upcast
    as they are used."""
    s = u.shape[0]
    heads, n_kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = heads // n_kv
    at = jnp.arange(s)
    qb = math.gcd(s, QUERY_BLOCK)
    wq, wkv, wo = seg["wq"][l], seg["wkv"][l], seg["wo"][l]

    def key_head(out, kh):
        mine = (u @ _cols(wq, kh * group * d, group * d, bits)).reshape(
            s, group, d)
        keys = u @ _cols(wkv, kh * d, d, bits)                  # (S, D)
        vals = u @ _cols(wkv, (n_kv + kh) * d, d, bits)

        def queries(q0):
            """One block of queries against every key, masked."""
            qs = jax.lax.dynamic_slice_in_dim(mine, q0, qb)     # (qb, G, D)
            a = jnp.einsum("qgd,kd->gqk", qs, keys) / math.sqrt(d)
            seen = at[None, :] <= (q0 + jnp.arange(qb))[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], a, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->qgd", p, vals)

        a = jax.lax.map(queries, jnp.arange(0, s, qb))
        a = a.reshape((s,) + a.shape[2:])                       # (S, G, D)
        w = _w(jax.lax.dynamic_slice_in_dim(wo, kh * group, group, 0), bits)
        return out + jnp.einsum("qgd,gde->qe", a, w), None

    out, _ = jax.lax.scan(key_head, jnp.zeros_like(u), jnp.arange(n_kv))
    return out


def _route(scores, bias, cfg):
    """(S, experts) float32 sigmoid scores -> (S, experts) weights, zero
    for the experts a token did not choose: the ``top_k`` largest ``scores
    + bias``, each SCORE over their sum, times the scaling factor."""
    s = scores.shape[0]
    chosen = jnp.argsort(-(scores + bias[None]), axis=-1)[:, :cfg.top_k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], chosen].set(
        w * cfg.routed_scale)


def _relu2(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def _experts(seg, l, u, cfg, bits):
    """The expert part on the norm ``u`` (S, E)."""
    weights = _route(jax.nn.sigmoid(u @ _w(seg["router"][l], bits)),
                     seg["bias"][l].astype(jnp.float32), cfg)
    first, count = cfg.experts_held or (0, cfg.n_routed_experts)
    ex, sh = seg["experts"], seg["shared"]
    latent = u @ _w(seg["w1"][l], bits)

    def routed(e, y):
        # This chip's share: the experts it holds, one at a time.
        w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
        return y + w[:, None] * _relu2(latent, _w(ex["w_up"][l, e], bits),
                                       _w(ex["w_down"][l, e], bits))

    y = jax.lax.fori_loop(0, count, routed, jnp.zeros_like(latent))
    return y @ _w(seg["w2"][l], bits) + _relu2(
        u, _w(sh["w_up"][l], bits), _w(sh["w_down"][l], bits))


_PARTS = {"M": _mamba, "*": _attention, "E": _experts}


@partial(jax.jit, static_argnames=("cfg", "letter", "bits"))
def _layer(seg, l, x, cfg, letter, bits):
    with jax.default_matmul_precision("highest"):
        u = _rms(x, seg["norm"][l].astype(jnp.float32), cfg.norm_eps)
        return x + _PARTS[letter](seg, l, u, cfg, bits)


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _head(params, x, cfg, bits):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x @ _w(params["head"], bits)


def logits(params: Dict[str, Any], tokens, cfg,
           bits: Optional[int] = None, rows=None) -> jax.Array:
    """Token ids (S,) of ONE sequence -> float32 logits (S, V), causal;
    under ``rows`` the logits at those positions only."""
    x = params["tok_embed"][jnp.asarray(tokens)].astype(jnp.float32)
    if bits is not None:
        # The embedding's scale is per column over the whole table.
        x = _w(params["tok_embed"], bits)[jnp.asarray(tokens)]
    for seg, l, letter in _layers(cfg):
        x = _layer(params["segments"][seg], l, x, cfg, letter, bits)
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
