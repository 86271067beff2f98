"""Plain reference of the decoder the serve and train cells run: float32
``jax.numpy``, no kernels, no cache, no batching tricks, matmuls at the
highest precision. It follows the published InternLM2 / Llama block
(RMSNorm, rotary embeddings over the pairs ``(i, i + head_dim / 2)``,
grouped-query attention, SwiGLU). Departure:
InternLM2 packs q, k and v into one ``wqkv``; the program holds them apart
(or fused along heads), which is the same mathematics."""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D); rotate pairs (i, i + D/2), as the program does."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(h, layer, n_heads, n_kv):
    if "wqkv" in layer:
        qkv = jnp.einsum("bse,ehd->bshd", h, layer["wqkv"])
        return (qkv[:, :, :n_heads], qkv[:, :, n_heads:n_heads + n_kv],
                qkv[:, :, n_heads + n_kv:])
    return (jnp.einsum("bse,ehd->bshd", h, layer["wq"]),
            jnp.einsum("bse,ehd->bshd", h, layer["wk"]),
            jnp.einsum("bse,ehd->bshd", h, layer["wv"]))


def logits(params: Dict[str, Any], tokens, cfg) -> jax.Array:
    """Token ids (B, S) -> float32 logits (B, S, V), causal."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["tok_embed"][tokens]
        s = tokens.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        rep = cfg.n_heads // cfg.n_kv_heads

        def block(x, layer):
            h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
            q, k, v = _qkv(h, layer, cfg.n_heads, cfg.n_kv_heads)
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + jnp.einsum("bshd,hde->bse", a, layer["wo"])
            h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
            if "w_gate_up" in layer:
                gate, up = jnp.split(h @ layer["w_gate_up"], 2, -1)
            else:
                gate, up = h @ layer["w_gate"], h @ layer["w_up"]
            return x + (jax.nn.silu(gate) * up) @ layer["w_down"], None

        x, _ = jax.lax.scan(block, x, p["layers"])
        x = _rms_norm(x, p["final_norm"], cfg.norm_eps)
        return x @ p["lm_head"]


def loss(params, tokens, cfg) -> jax.Array:
    """Mean next-token cross entropy of ``tokens`` (B, S + 1)."""
    lg = logits(params, tokens[:, :-1], cfg)
    logz = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - gold)


def served_token_margins(params, cfg, prompts: List[List[int]],
                         answers: List[List[int]]) -> List[float]:
    """For each served token, ``max(logits) - logits[token]`` at its
    position under the reference, teacher-forced on prompt + answer. Rows
    are padded on the right to one length (causal, so padding changes
    nothing before it)."""
    width = max(len(p) + len(a) for p, a in zip(prompts, answers))
    rows = np.zeros((len(prompts), width), np.int32)
    for i, (p, a) in enumerate(zip(prompts, answers)):
        rows[i, :len(p) + len(a)] = list(p) + list(a)
    lg = np.asarray(jax.jit(lambda pr, t: logits(pr, t, cfg))(
        params, jnp.asarray(rows)))
    out = []
    for i, (p, a) in enumerate(zip(prompts, answers)):
        for j, tok in enumerate(a):
            row = lg[i, len(p) + j - 1]
            out.append(float(row.max() - row[tok]))
    return out


def greedy_answers(params, cfg, prompts: List[List[int]], n: int
                   ) -> List[List[int]]:
    """``n`` greedy tokens after each prompt under this reference: the
    reference put in the program's place. One padded width for every step,
    so one compile."""
    width = max(len(p) for p in prompts) + n
    rows = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        rows[i, :len(p)] = p
    step = jax.jit(lambda pr, t: logits(pr, t, cfg))
    answers: List[List[int]] = [[] for _ in prompts]
    for j in range(n):
        best = np.asarray(jnp.argmax(step(params, jnp.asarray(rows)), -1))
        for i, p in enumerate(prompts):
            tok = int(best[i, len(p) + j - 1])
            answers[i].append(tok)
            rows[i, len(p) + j] = tok
    return answers


def rounded_weights(params, bits: int = 8):
    """Every matrix rounded to ``bits``-bit integers, symmetric, one scale
    per layer and per index of its last axis, and back to float32: what
    weight-only quantization would serve. Norm scales stay. The control of
    ``correct``: the replica computes in bfloat16, and int8 is the nearest
    precision below it."""
    top = 2.0 ** (bits - 1) - 1

    def one(path, a):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.endswith("norm"):
            return a
        a = a.astype(jnp.float32)
        # A layer's tensors are stacked along a leading layer axis.
        axes = tuple(range(1 if a.ndim > 2 else 0, a.ndim - 1))
        scale = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / top
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.round(a / scale) * scale

    return jax.tree_util.tree_map_with_path(one, params)
