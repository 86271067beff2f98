"""Plain reference of DeepSeek-V2 as the ``deepseek-v2`` cells serve it:
float32 ``jax.numpy``, matmuls at the highest precision, no cache, no
absorbed form, no batching, one sequence at a time. It follows the
published ``modeling_deepseek.py``:

* MLA: ``c_q = RMSNorm(W_DQ x)``, ``q = W_UQ c_q`` split per head into
  ``q_nope | q_pe``; ``[c_kv | k_pe] = W_DKV x``, ``c_kv = RMSNorm(c_kv)``,
  ``[k_nope | v] = W_UKV c_kv`` per head, ``k_pe`` one row for all heads;
  RoPE with YaRN on ``q_pe`` and ``k_pe``; scores ``(q_nope . k_nope + q_pe
  . k_pe) * softmax_scale``, ``softmax_scale = qk_head_dim^-0.5 * m^2``, ``m
  = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; ``W_O``.
* Layer < ``first_k_dense_replace``: SwiGLU. Others: ``s = softmax(W_G
  x)``; the groups are scored by their best expert, the ``topk_group`` best
  groups kept, the ``num_experts_per_tok`` best experts among them chosen;
  weights ``s`` of the chosen (renormalised only under ``norm_topk_prob``)
  times ``routed_scaling_factor``; ``y = sum_e w_e SwiGLU_e(x) +
  SwiGLU_shared(x)``.

Departures, all noted in the configuration file: the chip's SHARE (the
sum runs over the experts held here, the vocabulary is the rows held
here), as the model-configs guide prescribes for the program and the
reference alike; the training-only auxiliary losses are left out. Rotary
pairs are ``(2i, 2i + 1)`` as published (the published code de-interleaves
before rotating, which a dot product does not see).

The routing is this file's own, from its own float32 scores. Nothing of
``ray_tpu.models`` or ``ray_tpu.ops`` is used. The weights are the
replica's tree (bfloat16), upcast a layer and an expert at a time, so the
reference fits beside a served model that fills the chip; attention goes
by blocks of heads and of queries, one at a time, for the same reason (a
block's scores are 16 x 512 x S float32: 0.27 GB at the check's longest
prompt of 8,200 tokens, where the scores whole would be 34 GB), and the
head is taken at the rows that are asked for (8,200 x 25,600 float32
would be 0.84 GB)."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 16
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


def yarn_inv_freq(cfg):
    """The published ``DeepseekV2YarnRotaryEmbedding``, transcribed."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max_len
                               / (rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    exponent = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exponent)
    freq_inter = 1.0 / (cfg.rope_factor * base ** exponent)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rope(x, cfg):
    """``x`` (S, ..., rope): rotate the pairs ``(2i, 2i + 1)`` by the
    position along the first axis."""
    s = x.shape[0]
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(cfg))[None, :])
    ratio = (_mscale(cfg.rope_factor, cfg.rope_mscale)
             / _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    shape = (s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * ratio).reshape(shape), \
        (jnp.sin(ang) * ratio).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(group, l, x, cfg, bits):
    """MLA of layer ``l`` of ``group`` on one sequence ``x`` (S, E)."""
    s = x.shape[0]
    nope, rope, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.kv_lora_rank)
    h = _rms_norm(x, group["attn_norm"][l], cfg.norm_eps)
    c_q = _rms_norm(h @ _w(group["q_a"][l], bits), group["q_norm"][l],
                    cfg.norm_eps)
    ckv = h @ _w(group["kv_a"][l], bits)
    c_kv = _rms_norm(ckv[:, :r], group["kv_norm"][l], cfg.norm_eps)
    k_pe = _rope(ckv[:, r:], cfg)                            # (S, rope)
    m = _mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    scale = (nope + rope) ** -0.5 * m * m
    at = jnp.arange(s)
    qb = math.gcd(s, QUERY_BLOCK)
    hb = math.gcd(cfg.n_heads, HEAD_BLOCK)

    def by_heads(w):
        """(r, H, d) -> (H / hb, r, hb, d): the leading axis for the map."""
        return jnp.moveaxis(w.reshape(w.shape[0], -1, hb, w.shape[2]), 1, 0)

    def heads(out, weights):
        """One block of heads adds its share of ``W_O concat_h(P v)``."""
        q_b, kv_b, wo = weights
        q = jnp.einsum("sr,rhd->shd", c_q, _w(q_b, bits))
        kv = jnp.einsum("sr,rhd->shd", c_kv, _w(kv_b, bits))
        q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def queries(q0):
            """One block of queries against every key, masked."""
            qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, qb)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, q0, qb)
            sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                  + jnp.einsum("qhd,kd->hqk", qp, k_pe)) * scale
            seen = at[None, :] <= (q0 + jnp.arange(qb))[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        a = jax.lax.map(queries, jnp.arange(0, s, qb))
        return out + jnp.einsum("qhd,hde->qe",
                                a.reshape((s,) + a.shape[2:]),
                                _w(wo, bits)), None

    # One block at a time (a scan, a ``lax.map``): blocks written out side
    # by side are scheduled side by side, and their scores live together.
    wo = group["wo"][l]
    out, _ = jax.lax.scan(heads, x, (by_heads(group["q_b"][l]),
                                     by_heads(group["kv_b"][l]),
                                     wo.reshape((-1, hb) + wo.shape[1:])))
    return out


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _route(scores, cfg):
    """(S, experts) float32 scores -> (S, experts) weights, zero for the
    experts a token did not choose."""
    s, e = scores.shape
    best = scores.reshape(s, cfg.n_group, e // cfg.n_group).max(-1)
    kept = jnp.argsort(-best, axis=-1)[:, :cfg.topk_group]
    open_ = jnp.zeros((s, cfg.n_group), bool).at[
        jnp.arange(s)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(open_, e // cfg.n_group, axis=1),
                       scores, 0.0)
    chosen = jnp.argsort(-masked, axis=-1)[:, :cfg.top_k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.top_k > 1 and cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg.routed_scaling_factor
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], chosen].set(w)


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _dense_layer(group, l, x, cfg, bits):
    with jax.default_matmul_precision("highest"):
        x = _attention(group, l, x, cfg, bits)
        h = _rms_norm(x, group["mlp_norm"][l], cfg.norm_eps)
        return x + _swiglu(h, _w(group["w_gate"][l], bits),
                           _w(group["w_up"][l], bits),
                           _w(group["w_down"][l], bits))


@partial(jax.jit, static_argnames=("cfg", "bits"))
def _moe_layer(group, l, x, cfg, bits):
    with jax.default_matmul_precision("highest"):
        x = _attention(group, l, x, cfg, bits)
        h = _rms_norm(x, group["mlp_norm"][l], cfg.norm_eps)
        scores = jax.nn.softmax(h @ _w(group["router"][l], bits), -1)
        weights = _route(scores, cfg)
        first, count = cfg.held
        ex = group["experts"]

        def one(e, y):
            # This chip's share: the experts it holds, one at a time.
            w = jax.lax.dynamic_index_in_dim(weights, first + e, 1, False)
            return y + w[:, None] * _swiglu(
                h, _w(ex["w_gate"][l, e], bits), _w(ex["w_up"][l, e], bits),
                _w(ex["w_down"][l, e], bits))

        routed = jax.lax.fori_loop(0, count, one, jnp.zeros_like(h))
        sh = group["shared"]
        shared = _swiglu(h, _w(sh["w_gate"][l], bits),
                         _w(sh["w_up"][l], bits), _w(sh["w_down"][l], bits))
        return x + routed + shared


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
    for l in range(cfg.n_dense_layers):
        x = _dense_layer(params["dense"], l, x, cfg, bits)
    for l in range(cfg.n_moe_layers):
        x = _moe_layer(params["moe"], l, x, cfg, bits)
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
    """The control's tokens and their margins. With its weights rounded to
    ``bits`` bits this reference answers ONE token after each of the last
    ``n`` cuts of every prompt (``prompt[:L - n + 1 + j]``, the last cut
    the prompt whole): what a replica of that precision would serve there.
    One causal forward gives all ``n`` (the logits at position ``L - n + j``
    are what the cut there is answered from), so a control over hundreds
    of tokens costs two forwards a prompt, where ``n`` tokens answered one
    after another cost ``n``. Returned is each such token's margin under
    the UNROUNDED reference at the same position."""
    padded = _padded(prompts, max(len(p) for p in prompts))
    out = []
    for row, p in zip(padded, prompts):
        at = np.arange(len(p) - n, len(p))
        said = np.asarray(logits(params, row, cfg, bits, rows=at)).argmax(-1)
        lg = np.asarray(logits(params, row, cfg, rows=at))
        out += [float(lg[j].max() - lg[j, tok]) for j, tok in enumerate(said)]
    return out
