"""The programs ``serve/decode.py`` runs for Command A+
(``cohere2_moe.py``): what the engine asks of a model module
(docs/SERVING.md, "The model seam"), over a paged pool of TWO KINDS of page
that are the same size a token a layer.

Every layer caches 8 key heads and 8 value heads of 128 a token, FLAT on a
page's last axis (1,024 lanes each). A full layer keeps every token; a
window layer is read no further back than ``window`` - 1 = 4,095 tokens, so
its pages behind that are dead: ``page_kinds`` says so to the engine, which
keeps an allocator and a block table a kind and hands a slot's dead window
pages back at the step that passes them. At pages of 64 tokens a slot keeps
65 window pages between steps. The pool is ``{"full_k", "full_v",
"window_k", "window_v"}``, each ``[layers of the kind, pages of the kind +
1, page_tokens, 1024]``.

The block is PARALLEL: a layer takes one LayerNorm of the stream, the
attention and the feed-forward both read it, and one add takes both. In a
decode step the attention's cache reads and the experts' matmuls of one
layer do not wait for each other.

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``): a chunk's keys
  and values are written through pages of both kinds, then a full layer
  attends over the row's ``W`` full pages and a window layer over the
  window pages round the chunk (``block_tables["window"]``, whose column 0
  is the sequence's page ``block_tables["window_first"]``), both through
  ``ops/chunk_attention.py``.
* **decode** (``paged_decode_step``): both kinds' pages are read where they
  lie, by the kernel that scores them (``ops/paged_decode_attention.py``):
  the full layer hands it ``moe_decode``'s groups of one slot's pages, a
  window layer the slot's 65 pages in lists of ``VIEW_GROUP``; no program
  copies a slot's pages first.

The layers ride one ``scan`` a segment (``moe_decode.scan_segments``) with
the segment's kind of pool in the carry. The engine's optional program
(``shard_decode_state``) is not here: the engine refuses a mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.models.cohere2_moe import (FLOAT32_LEAVES, FULL, WINDOW,
                                        Cohere2MoeConfig)
# Shared with every model the engine runs, and part of what this module
# provides: the prefill buckets and the fused sampler.
from ray_tpu.models.llama_decode import (cache_bucket,  # noqa: F401
                                         sample_batch)
# The full kind's view in groups of one slot's pages, and the rows it
# needs (the engine asks for ``view_rows``).
from ray_tpu.models.moe_decode import VIEW_GROUP, view_rows  # noqa: F401
from ray_tpu.ops import moe
from ray_tpu.ops.chunk_attention import chunk_attention
from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                paged_decode_attention)
from ray_tpu.ops.rotary import rope_at, rotate_pairs
from ray_tpu.parallel.sharding import constrain

Pool = Dict[str, jax.Array]

# What ``paged_decode_step`` counts beside its logits, summed over the
# layers, under ``deepseek_decode``'s names.
STEP_STATS = moe_decode.MOE_STEP_STATS


def page_kinds(config: Cohere2MoeConfig) -> Dict[str, Dict[str, Any]]:
    """The kinds of page this model's pool has, the one that keeps
    everything first: for each, the ``window`` of tokens a page has to
    outlive (``None``: all of them) and the pool's ``leaves`` it indexes."""
    return {FULL: {"window": None, "leaves": ("full_k", "full_v")},
            WINDOW: {"window": config.window,
                     "leaves": ("window_k", "window_v")}}


def compute_weights(params: Dict[str, Any], config: Cohere2MoeConfig,
                    donate: bool = False) -> Dict[str, Any]:
    """``params`` with every matrix in ``config.dtype`` (norm scales stay
    float32)."""
    return moe_decode.cast_weights(params, config.dtype, FLOAT32_LEAVES,
                                   donate)


def init_page_pool(config: Cohere2MoeConfig, pages: Dict[str, int],
                   page_tokens: int, dtype=None) -> Pool:
    """Zeroed pool: for each kind ``pages[kind]`` usable pages and the
    scratch page 0, a layer of the kind each."""
    c = config
    return {f"{kind}_{part}": jnp.zeros(
        (c.kind_layers(kind), pages[kind] + 1, page_tokens, c.kv_width),
        dtype or c.dtype) for kind in (FULL, WINDOW) for part in "kv"}


live_page_view = moe_decode.kinds_page_view


# ------------------------------------------------------------ layer pieces


def _layer_norm(x, scale, c: Cohere2MoeConfig):
    """LayerNorm without a bias, float32: the mean goes, the variance is
    about it."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + c.norm_eps) * scale


def _qkv(layer, h, c: Cohere2MoeConfig, kind: str, cos, sin):
    """``h`` (B, S, E) through the fused projection: ``q`` (B, S, H, D) and
    the rows that are cached, ``k`` and ``v`` (B, S, KV x D), heads flat. A
    window layer turns the pairs ``(2i, 2i + 1)`` of every head of ``q``
    and ``k`` by the position (the halves come back de-interleaved, alike
    in both, which a dot product does not see); a full layer has no
    position term."""
    B, S, _ = h.shape
    nq = c.n_heads * c.head_dim
    qkv = jnp.einsum("bse,ef->bsf", h, layer["wqkv"])
    q = qkv[..., :nq].reshape(B, S, c.n_heads, c.head_dim)
    k = qkv[..., nq:nq + c.kv_width]
    if kind == WINDOW:
        k = k.reshape(B, S, c.n_kv_heads, c.head_dim)
        q, k = (rotate_pairs(t, cos[:, :, None], sin[:, :, None],
                             interleaved=True).astype(h.dtype)
                for t in (q, k))
        k = k.reshape(B, S, c.kv_width)
    return q, k, qkv[..., nq + c.kv_width:]


def _attn_out(layer, att, c: Cohere2MoeConfig):
    """``att`` (B, S, H, D) -> the attention's half of the block (B, S,
    E)."""
    # The pre-contraction anchors of ``llama_decode`` (no-ops without a
    # mesh, which this model has no rules for): no contraction is split.
    att = constrain(att.astype(c.dtype),
                    ("batch", "length", "attn_heads", "head_dim"))
    return jnp.einsum("bshd,hde->bse", att, layer["wo"])


def _swiglu(w, x):
    gate = jnp.einsum("bse,em->bsm", x, w["w_gate"])
    up = jnp.einsum("bse,em->bsm", x, w["w_up"])
    ffn = constrain(jax.nn.silu(gate) * up,
                    ("batch", "length", "mlp_hidden"))
    return jnp.einsum("bsm,me->bse", ffn, w["w_down"])


def _ffn(layer, normed, c: Cohere2MoeConfig, keep):
    """The feed-forward's half of the block on ``normed`` (B, S, E)
    float32, the SAME norm the attention read: the held experts' part of
    the routed sum plus the shared experts' AVERAGE. ``keep`` (B, S) bool:
    tokens that are real. Returns ``(f, stats)``."""
    shape = normed.shape
    h = normed.astype(c.dtype)
    with jax.named_scope("moe_route"):
        # The gate is float32, on the norm before it is rounded.
        logits = jnp.einsum("te,en->tn", normed.reshape(-1, shape[-1]),
                            layer["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, weights = moe.route(logits, c.router())
    with jax.named_scope("moe_experts"):
        routed, sizes = moe.held_experts_ffn(
            h.reshape(-1, shape[-1]), idx, weights, layer["experts"],
            c.held, keep.reshape(-1), layer=layer["expert_layer"],
            router=c.router())
    with jax.named_scope("moe_shared"):
        # The leaf is the shared experts side by side, so one SwiGLU is
        # their sum; the quarter makes it their average, in float32.
        shared = _swiglu(layer["shared"], h).astype(jnp.float32) \
            / c.n_shared_experts
    return (routed.reshape(shape).astype(jnp.float32) + shared,
            moe_decode.moe_step_stats(sizes))


def _head(params, x, c: Cohere2MoeConfig):
    """(B, E) -> float32 logits (B, V) through the tied embedding, times
    ``logit_scale``."""
    x = _layer_norm(x, params["final_norm"], c).astype(c.dtype)
    return c.logit_scale * jnp.einsum(
        "be,ve->bv", x, params["tok_embed"],
        preferred_element_type=jnp.float32)


def _flat_queries(q, c: Cohere2MoeConfig):
    """``q`` (B, H, D) -> (B, H, KV x D), each head's query over all key
    heads' lanes (``moe_decode.flat_queries``)."""
    return moe_decode.flat_queries(q, c.n_kv_heads, c.n_heads, c.head_dim)


def _own_values(part, c: Cohere2MoeConfig):
    """(B, H, KV x D) weighted values over all lanes -> (B, H, D)."""
    return moe_decode.own_values(part, c.n_kv_heads, c.n_heads, c.head_dim)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: Dict[str, jax.Array],
                         config: Cohere2MoeConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Pool]:
    """Right-padded ``tokens`` (B, S) from ``pos = prefix_lens``: the
    chunked-prefill continuation and (from 0) the whole prefill.
    ``block_tables`` maps both kinds: ``"full"`` (B, W) the row's leading
    full pages, ``"window"`` (B, Ww) its window pages from the sequence's
    page ``"window_first"`` (B,) on, which cover the chunk and the
    ``window`` - 1 tokens before it. Each layer scatters its new rows into
    its kind's pages and attends through ``chunk_attention``: a full layer
    over the ``W`` pages (tiles above a row's frontier are skipped), a
    window layer over the ``Ww`` pages. Returns the logits at each row's
    last real token and the pool."""
    c = config
    B, S = tokens.shape
    T = pool["full_k"].shape[2]
    tables = {FULL: block_tables[FULL], WINDOW: block_tables[WINDOW]}
    first = {FULL: jnp.zeros((B,), jnp.int32),
             WINDOW: block_tables["window_first"].astype(jnp.int32)}
    x = params["tok_embed"][tokens].astype(jnp.float32)      # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # (B, S)
    rows = jnp.arange(B)[:, None]
    offs = abs_pos % T
    keep = jnp.arange(S)[None, :] < (lengths - prefix_lens)[:, None]
    pages = {}
    for kind, bt in tables.items():
        # A position outside the kind's columns goes to the scratch page,
        # never a clamped real one.
        col = abs_pos // T - first[kind][:, None]
        width = bt.shape[1]
        pages[kind] = jnp.where(
            (col >= 0) & (col < width),
            bt[rows, jnp.clip(col, 0, width - 1)], 0)
    rope = rope_at(abs_pos, c.inv_freq)

    def body(seg, x, k_pool, v_pool, layer, base):
        kind = seg.kind
        kv, bt = c.n_kv_heads, tables[kind]
        normed = _layer_norm(x, layer["norm"], c)
        with jax.named_scope("attn_proj"):
            q, k_new, v_new = _qkv(layer, normed.astype(c.dtype), c, kind,
                                   *rope)
        # The gathers follow the scatter, so the chunk sees itself.
        k_pool = k_pool.at[base + pages[kind], offs].set(
            k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[base + pages[kind], offs].set(
            v_new.astype(v_pool.dtype))
        with jax.named_scope(f"{kind}_gather"):
            keys = bt.shape[1] * T
            k_all = k_pool[base + bt].reshape(B, keys, kv, c.head_dim)
            v_all = v_pool[base + bt].reshape(B, keys, kv, c.head_dim)
        att = chunk_attention(
            q.transpose(0, 2, 1, 3), k_all.transpose(0, 2, 1, 3),
            v_all.transpose(0, 2, 1, 3), prefix_lens, first[kind] * T,
            c.softmax_scale, window=c.window if kind == WINDOW else None)
        with jax.named_scope("attn_proj"):
            a = _attn_out(layer, att.transpose(0, 2, 1, 3), c)
        f, stats = _ffn(layer, normed, c, keep)
        # The parallel block's one add.
        return x + a.astype(jnp.float32) + f, k_pool, v_pool, stats

    x, pool, _ = moe_decode.scan_segments(body, x, c.segments(),
                                          params["segments"], pool)
    idx = jnp.clip(lengths - prefix_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _head(params, x_last, c), pool


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: Dict[str, jax.Array],
                  config: Cohere2MoeConfig,
                  lengths: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Pool]:
    """Whole prefill of right-padded prompts (B, S): the suffix program
    from position 0."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    return paged_prefill_suffix(params, tokens, pool, block_tables, config,
                                jnp.zeros((B,), jnp.int32), lengths)


# ------------------------------------------------------------------- decode


def paged_decode_step(params: Dict[str, Any], pool: Pool,
                      view: Dict[str, jax.Array], lengths: jax.Array,
                      tokens: jax.Array, config: Cohere2MoeConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array, jax.Array]:
    """One token per slot. ``tokens`` (B,) are written at ``lengths[b]``;
    ``view`` is ``live_page_view``'s pair. Both kinds' pages go to
    ``paged_decode_attention`` as lists of ``VIEW_GROUP`` pages of one
    slot, built once for every layer of the kind: the full kind's are the
    view's groups, a window layer's the slot's ``R`` last window pages cut
    into lists (the kernel adds a slot's lists up and fetches no page that
    lies outside ``0 <= i - j < window``). A slot that owns no row of the
    full kind's list writes to the scratch pages, is left out of the
    experts' pairs and gets finite junk logits. Returns ``(logits, pool,
    lengths + 1, stats)``."""
    c = config
    B = tokens.shape[0]
    T = pool["full_k"].shape[2]
    pages, owner, index = view[FULL][0], view[FULL][1], view[FULL][2]
    w_pages, w_index = view[WINDOW][0], view[WINDOW][1]      # (B, R)
    N, G, R = pages.shape[0], VIEW_GROUP, w_pages.shape[1]
    if N % G:
        raise ValueError(f"a view of {N} rows is not whole groups of {G}")
    pos = lengths
    x = params["tok_embed"][tokens].astype(jnp.float32)[:, None]  # (B,1,E)
    member = owner[None, :] == jnp.arange(B)[:, None]        # (B, N)
    steps = member.any(axis=1)                               # (B,)
    off = pos % T
    # The page a slot writes, a kind: the one at index pos // T among its
    # rows, else the scratch page.
    write = {
        FULL: jnp.sum(jnp.where(
            member & (index[None, :] == (pos // T)[:, None]),
            pages[None, :], 0), axis=1),
        WINDOW: jnp.sum(jnp.where(w_index == (pos // T)[:, None],
                                  w_pages, 0), axis=1)}
    rope = rope_at(pos[:, None], c.inv_freq)
    # A slot's R window pages as whole lists of G: the entries that pad
    # the last one are no page (index -1), which the kernel never fetches.
    per = -(-R // G)
    short = ((0, 0), (0, per * G - R))
    lists = {
        FULL: page_lists(pages.reshape(N // G, G),
                         owner.reshape(N // G, G)[:, 0],
                         index.reshape(N // G, G), pos, T),
        WINDOW: page_lists(
            jnp.pad(w_pages, short).reshape(B * per, G),
            jnp.repeat(jnp.arange(B, dtype=jnp.int32), per),
            jnp.pad(w_index, short, constant_values=-1).reshape(
                B * per, G), pos, T, c.window)}

    def body(seg, x, k_pool, v_pool, layer, base):
        kind = seg.kind
        normed = _layer_norm(x, layer["norm"], c)
        with jax.named_scope("attn_proj"):
            q, k_new, v_new = _qkv(layer, normed.astype(c.dtype), c, kind,
                                   *rope)
        k_pool = k_pool.at[base + write[kind], off].set(
            k_new[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[base + write[kind], off].set(
            v_new[:, 0].astype(v_pool.dtype))
        with jax.named_scope(f"{kind}_attn"):
            _, total, part = paged_decode_attention(
                _flat_queries(q[:, 0], c).astype(k_pool.dtype), k_pool,
                v_pool, lists[kind], c.softmax_scale, base)
            total = total[..., None]
            att = jnp.where(total > 0.0, _own_values(part, c) / total, 0.0)
        with jax.named_scope("attn_proj"):
            a = _attn_out(layer, att[:, None], c)
        f, stats = _ffn(layer, normed, c, steps[:, None])
        return x + a.astype(jnp.float32) + f, k_pool, v_pool, stats

    x, pool, stats = moe_decode.scan_segments(body, x, c.segments(),
                                              params["segments"], pool)
    return _head(params, x[:, 0], c), pool, pos + 1, stats
