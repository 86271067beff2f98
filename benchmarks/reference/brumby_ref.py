"""Plain reference of Brumby-14B-Base as the ``brumby-14b`` cell serves it:
float32 ``jax.numpy``, matmuls at the highest precision, the QUADRATIC form
of power retention with its mask and its cumulative gates, EVERY layer at
EVERY position, no state, no chunks, no cache, no kernels, no batching, one
sequence at a time. Written from the published ``config.json``
(``model_type`` ``brumby``) and arXiv:2507.04239 with the gating of the
release's retention kernels. A layer, for input ``x`` (RMSNorm, eps 1e-6):

* ``u = RMSNorm(x)``; ``q = W_q u`` (H heads of d), ``k = W_k u``, ``v =
  W_v u`` (J heads of d), no biases; an RMSNorm over each head of ``q`` and
  of ``k``; rotary (base ``rope_theta``, the whole head, pairs ``(i, i + d /
  2)``) on both; ``gam = log sigmoid(W_g u)``, one a key-value head, no bias.
* For query head ``h`` of key-value head ``j = h // (H / J)``, with ``s =
  1 / sqrt(d)`` and ``G_t = gam_1 + ... + gam_t``: ``a[t, i] = (s q_t^h .
  k_i^j)^2 exp(G_t - G_i)`` for ``i <= t``; ``o_t^h = sum_i a[t, i] v_i^j /
  (sum_i a[t, i] + eps)``.
* ``h = x + W_o [o^1 .. o^H]``; ``y = h + W_down(silu(W_gate RMSNorm(h)) *
  W_up RMSNorm(h))``. A final RMSNorm, an untied head.

No departure from the published form: the gate has no bias. The cell's
seeded weights get their gates of 0.9-0.999 from the weights alone (a
constant channel of the stream that one row of ``W_g`` reads:
``ray_tpu/models/brumby.py::_gate_channel``), which this file neither
knows nor needs to.

The program computes by STATE (the symmetric second power of a key, a
recurrence, sub-chunks) and this file by PAIRS, so each formulation checks
the other.

Nothing of ``ray_tpu.models`` or ``ray_tpu.ops`` is used; only the LAYOUT
of the replica's weight tree is (the layers stacked on a leading axis,
``wo`` as ``(H, d, E)``). The weights are upcast a piece of a layer at a
time (the mixer; the feed-forward a quarter of its width at a time; the
head a block of the vocabulary at a time, at the rows that are asked for)
and the pairs are taken a key-value head and ``QUERY_BLOCK`` queries at a
time, so the reference fits beside a served model that fills the chip."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 256
HEAD_BLOCK = 16384
QUERY_BLOCK = 256
MLP_BLOCKS = 4


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


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, positions, theta):
    """``x`` (S, heads, d) rotated at ``positions`` (S,): pairs ``(i, i +
    d / 2)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _at(tree, l):
    return {k: jax.lax.dynamic_index_in_dim(v, l, 0, False)
            for k, v in tree.items()}


def retention_pairs(q, k, v, log_g, scale: float, eps: float,
                    degree: int = 2, block: int = QUERY_BLOCK):
    """The quadratic form for ONE sequence: ``q`` (S, J, G, d), ``k`` and
    ``v`` (S, J, d), ``log_g`` (S, J), float32. Returns (S, J, G, d).
    A key-value head and ``block`` queries at a time."""
    S = q.shape[0]
    cum = jnp.cumsum(log_g, axis=0)                           # (S, J)
    pad = -S % block
    starts = jnp.arange(0, S + pad, block)

    def head(args):
        qj, kj, vj, cj = args                  # (S, G, d) (S, d) (S, d) (S,)
        qj = jnp.pad(qj, [(0, pad), (0, 0), (0, 0)])
        cq = jnp.pad(cj, [(0, pad)])

        def rows(t0):
            qt = jax.lax.dynamic_slice_in_dim(qj, t0, block, 0)
            ct = jax.lax.dynamic_slice_in_dim(cq, t0, block, 0)
            t = t0 + jnp.arange(block)
            seen = jnp.arange(S)[None, :] <= t[:, None]       # (block, S)
            s = jnp.einsum("tgd,id->tgi", qt, kj) * scale
            decay = jnp.where(seen, jnp.exp(jnp.where(
                seen, ct[:, None] - cj[None, :], 0.0)), 0.0)
            a = s ** degree * decay[:, None, :]
            return jnp.einsum("tgi,id->tgd", a, vj) \
                / (a.sum(-1) + eps)[..., None]

        return jax.lax.map(rows, starts).reshape(S + pad, *qj.shape[1:])[:S]

    out = jax.lax.map(head, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                             v.transpose(1, 0, 2), cum.T))
    return out.transpose(1, 0, 2, 3)


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _mixer(tree, l, x, cfg, bits):
    """One sequence ``x`` (S, E) -> ``x + W_o o``."""
    with jax.default_matmul_precision("highest"):
        p = _at(tree, l)
        S = x.shape[0]
        H, J, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        u = _rms(x, p["norm1"], cfg.norm_eps)
        pos = jnp.arange(S)
        q = _rms((u @ _w(p["wq"], bits)).reshape(S, H, d), p["q_norm"],
                 cfg.norm_eps)
        k = _rms((u @ _w(p["wk"], bits)).reshape(S, J, d), p["k_norm"],
                 cfg.norm_eps)
        q = _rotate(q, pos, cfg.rope_theta).reshape(S, J, H // J, d)
        k = _rotate(k, pos, cfg.rope_theta)
        v = (u @ _w(p["wv"], bits)).reshape(S, J, d)
        log_g = jax.nn.log_sigmoid(u @ _w(p["wg"], bits))
        o = retention_pairs(q, k, v, log_g, d ** -0.5, cfg.retention_eps,
                            cfg.degree)
        wo = _w(p["wo"].reshape(H * d, -1), bits)
        return x + o.reshape(S, H * d) @ wo


@partial(jax.jit, static_argnames=("cfg", "bits", "part"))
def _mlp_part(tree, l, x, down_scale, cfg, bits, part):
    """Quarter ``part`` of the feed-forward's width: its share of
    ``W_down(silu(W_gate h) * W_up h)``."""
    with jax.default_matmul_precision("highest"):
        p = _at({k: tree[k] for k in ("norm2", "w_gate", "w_up", "w_down")},
                l)
        f = cfg.mlp_dim // MLP_BLOCKS
        cols = slice(part * f, (part + 1) * f)
        h = _rms(x, p["norm2"], cfg.norm_eps)
        mid = jax.nn.silu(h @ _w(p["w_gate"][:, cols], bits)) \
            * (h @ _w(p["w_up"][:, cols], bits))
        return mid @ _w(p["w_down"][cols], bits, down_scale)


@partial(jax.jit, static_argnames=("bits",))
def _down_scale(w_down, l, bits):
    return _scale(jax.lax.dynamic_index_in_dim(w_down, l, 0, False), bits)


@partial(jax.jit, static_argnames=("cfg",))
def _final_norm(params, x, cfg):
    return _rms(x, params["final_norm"], cfg.norm_eps)


@partial(jax.jit, static_argnames=("bits",))
def _head_block(x, cols, bits):
    with jax.default_matmul_precision("highest"):
        return x @ _w(cols, bits)


def logits(params: Dict[str, Any], tokens, cfg,
           bits: Optional[int] = None, rows=None) -> jax.Array:
    """Token ids (S,) of ONE sequence -> float32 logits (S, V), causal;
    under ``rows`` the logits at those positions only."""
    if cfg.mlp_dim % MLP_BLOCKS:
        raise ValueError(f"mlp_dim {cfg.mlp_dim} is not {MLP_BLOCKS} parts")
    embed = params["tok_embed"]
    # The embedding's scale is per column over the whole table.
    scale = None if bits is None else _scale(embed, bits)
    x = _w(embed[jnp.asarray(tokens)], bits, scale)
    tree = params["layers"]
    for l in range(cfg.n_layers):
        x = _mixer(tree, l, x, cfg, bits)
        down = None if bits is None else _down_scale(tree["w_down"], l, bits)
        x = x + sum(_mlp_part(tree, l, x, down, cfg, bits, part)
                    for part in range(MLP_BLOCKS))
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _final_norm(params, x, cfg)
    head = params["lm_head"]
    return jnp.concatenate(
        [_head_block(x, head[:, i:i + HEAD_BLOCK], bits)
         for i in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


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
