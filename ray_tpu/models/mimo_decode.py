"""The programs ``serve/decode.py`` runs for MiMo-V2 (``mimo.py``): what the
engine asks of a model module (docs/SERVING.md, "The model seam"), over a
paged pool of TWO KINDS of page.

A full layer caches every token's keys and values (4 heads: 768 + 512
numbers a token a layer); a window layer (8 heads: 1,536 + 1,024) is read
no further back than ``window`` - 1 tokens, so its pages behind that are
dead. ``page_kinds`` says so to the engine, which keeps an allocator and a
block table a kind and hands a slot's dead window pages back at the step
that passes them. The pool is ``{"full_k", "full_v", "window_k",
"window_v"}``, each ``[layers of the kind, pages of the kind + 1,
page_tokens, heads x width]``: a token's heads lie FLAT on the last axis
(768, 512, 1,536, 1,024: whole 128-lane tiles, where a last axis of 192
would be padded to 256 and a program that scatters into it would re-tile
the pool).

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``): a chunk's
  keys and values are written through pages of both kinds, then a full
  layer attends over the row's ``W`` full pages and a window layer over the
  few window pages round the chunk (``block_tables["window"]``, whose
  column 0 is the sequence's page ``block_tables["window_first"]``), both
  through ``ops/chunk_attention.py``.
* **decode** (``paged_decode_step``): a full layer reads the view's list of
  live full pages, grouped by slot as ``deepseek_decode``'s is, one matmul
  a group, in blocks of ``VIEW_BLOCK`` rows and only the blocks that hold
  live rows; a window layer reads its slot's two or three pages,
  ``view["window"]``, a fixed ``(2, slots, pages)``.

The layers ride one ``scan`` a segment (``MimoConfig.segments``) with the
segment's kind of pool in the carry, flat, so every program writes its new
rows into the donated buffer. The engine's optional program
(``shard_decode_state``) is not here: the engine refuses a mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import moe_decode
# Shared with every model the engine runs, and part of what this module
# provides: the prefill buckets and the fused sampler.
from ray_tpu.models.llama_decode import (cache_bucket,  # noqa: F401
                                         sample_batch)
from ray_tpu.models.mimo import (FLOAT32_LEAVES, FULL, WINDOW, MimoConfig,
                                 Segment)
# Shared with ``deepseek_decode``: the full kind's view in groups of one
# slot's pages, and the rows it needs (the engine asks for ``view_rows``).
from ray_tpu.models.moe_decode import VIEW_GROUP, view_rows  # noqa: F401
from ray_tpu.ops import moe
from ray_tpu.ops.chunk_attention import chunk_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import rope_at, rotate_pairs
from ray_tpu.parallel.sharding import constrain

Pool = Dict[str, jax.Array]

# Rows of the full kind's list that a decode step reads at a time.
VIEW_BLOCK = 512
# What ``paged_decode_step`` counts beside its logits, summed over the
# expert layers, under ``deepseek_decode``'s names.
STEP_STATS = moe_decode.MOE_STEP_STATS


def page_kinds(config: MimoConfig) -> Dict[str, Dict[str, Any]]:
    """The kinds of page this model's pool has, the one that keeps
    everything first: for each, the ``window`` of tokens a page has to
    outlive (``None``: all of them) and the pool's ``leaves`` it indexes."""
    return {FULL: {"window": None, "leaves": ("full_k", "full_v")},
            WINDOW: {"window": config.window,
                     "leaves": ("window_k", "window_v")}}


def compute_weights(params: Dict[str, Any], config: MimoConfig,
                    donate: bool = False) -> Dict[str, Any]:
    """``params`` with every matrix in ``config.dtype`` (norm scales, sink
    logits and the selection bias stay float32)."""
    return moe_decode.cast_weights(params, config.dtype, FLOAT32_LEAVES,
                                   donate)


def init_page_pool(config: MimoConfig, pages: Dict[str, int],
                   page_tokens: int, dtype=None) -> Pool:
    """Zeroed pool: for each kind ``pages[kind]`` usable pages and the
    scratch page 0, a layer of the kind each."""
    c = config
    pool = {}
    for kind in (FULL, WINDOW):
        lead = (c.kind_layers(kind), pages[kind] + 1, page_tokens)
        kv = c.kv_heads(kind)
        pool[f"{kind}_k"] = jnp.zeros(lead + (kv * c.head_dim,),
                                      dtype or c.dtype)
        pool[f"{kind}_v"] = jnp.zeros(lead + (kv * c.v_head_dim,),
                                      dtype or c.dtype)
    return pool


# ------------------------------------------------------------ layer pieces


def _embed(params, tokens):
    """The residual stream is float32 through the layers
    (``deepseek_decode._embed``); the matmuls read ``_normed``'s copy."""
    return params["tok_embed"][tokens].astype(jnp.float32)


def _normed(x, scale, c: MimoConfig):
    return rms_norm(x, scale, c.norm_eps).astype(c.dtype)


def _rotate(x, cos, sin, c: MimoConfig):
    """Rotary on the first ``rotary_dim`` of each head of ``x`` (B, S, H,
    D), pairs ``(i, i + rotary_dim / 2)``; the rest passes."""
    r = c.rotary_dim
    turned = rotate_pairs(x[..., :r], cos[:, :, None], sin[:, :, None])
    return jnp.concatenate([turned.astype(x.dtype), x[..., r:]], -1)


def _qkv(layer, h, c: MimoConfig, kind: str, cos, sin):
    """``h`` (B, S, E) through the fused projection: ``q`` (B, S, H, D)
    rotated, and the rows that are cached, ``k`` (B, S, KV x D) rotated and
    ``v`` (B, S, KV x Dv), heads flat."""
    B, S, _ = h.shape
    kv = c.kv_heads(kind)
    nq, nk = c.n_heads * c.head_dim, kv * c.head_dim
    qkv = jnp.einsum("bse,ef->bsf", h, layer["wqkv"])
    q = _rotate(qkv[..., :nq].reshape(B, S, c.n_heads, c.head_dim),
                cos, sin, c)
    k = _rotate(qkv[..., nq:nq + nk].reshape(B, S, kv, c.head_dim),
                cos, sin, c)
    return q, k.reshape(B, S, nk), qkv[..., nq + nk:]


def _attn_out(layer, att, c: MimoConfig):
    """``att`` (B, S, H, Dv) -> the block's output (B, S, E). The value
    scale multiplies here, once, in float32: the mathematics is that of
    scaled values."""
    att = (att.astype(jnp.float32) * c.value_scale).astype(c.dtype)
    # The pre-contraction anchors of ``llama_decode`` (no-ops without a
    # mesh, which this model has no rules for): no contraction is split.
    att = constrain(att, ("batch", "length", "attn_heads", "head_dim"))
    return jnp.einsum("bshd,hde->bse", att, layer["wo"])


def _swiglu(w, x):
    gate = jnp.einsum("bse,em->bsm", x, w["w_gate"])
    up = jnp.einsum("bse,em->bsm", x, w["w_up"])
    ffn = constrain(jax.nn.silu(gate) * up,
                    ("batch", "length", "mlp_hidden"))
    return jnp.einsum("bsm,me->bse", ffn, w["w_down"])


def _ffn(layer, x, c: MimoConfig, seg: Segment, keep):
    """The layer's feed-forward on the stream ``x`` (B, S, E): a dense
    SwiGLU, or the held experts' part of the routed ones (no shared
    expert). ``keep`` (B, S) bool: tokens that are real. Returns ``(x,
    stats)``."""
    if not seg.moe:
        return x + _swiglu(layer, _normed(x, layer["mlp_norm"], c)), 0.0
    shape = x.shape
    normed = rms_norm(x, layer["mlp_norm"], c.norm_eps)      # float32
    flat = normed.astype(c.dtype).reshape(-1, shape[-1])
    with jax.named_scope("moe_route"):
        # The gate is float32, on the stream before it is rounded.
        logits = jnp.einsum("te,en->tn", normed.reshape(-1, shape[-1]),
                            layer["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, weights = moe.route(logits, c.router(),
                                 bias=layer["router_bias"])
    with jax.named_scope("moe_experts"):
        routed, sizes = moe.held_experts_ffn(
            flat, idx, weights, layer["experts"], c.held,
            keep.reshape(-1), layer=layer["expert_layer"],
            router=c.router())
    return x + routed.reshape(shape), moe_decode.moe_step_stats(sizes)


def _scan_segments(body, x, params: Dict[str, Any], pool: Pool,
                   c: MimoConfig):
    """``moe_decode.scan_segments`` over this model's segments: one
    ``scan`` a segment with the pool of the segment's kind in its carry.
    Returns ``(x, pool, stats)``, the expert layers' ``STEP_STATS``
    summed."""
    return moe_decode.scan_segments(body, x, c.segments(),
                                    params["segments"], pool)


def _head(params, x, c: MimoConfig):
    """(B, E) -> float32 logits (B, V)."""
    x = _normed(x, params["final_norm"], c)
    return jnp.einsum("be,ev->bv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------- the decode's view


def live_page_view(block_tables: Dict[str, Any], counts: Dict[str, Any],
                   rows: Dict[str, int]) -> Dict[str, np.ndarray]:
    """The decode step's view of both kinds, built on the host.

    ``"full"``: ``moe_decode.live_page_view`` of the full kind's
    tables and counts on ``rows["full"]`` rows (the engine's ladder): a
    slot's pages in whole groups of ``VIEW_GROUP``.

    ``"window"``: ``(2, slots, rows["window"])`` int32, for each slot the
    pool pages of its last window pages and their indices in the
    sequence, ``counts["window"]`` being ``(first held index, pages held)``
    a slot; a slot that does not step (0 held) and the entries past a
    slot's pages are the scratch page at index -1, which no position
    matches."""
    return moe_decode.kinds_page_view(block_tables, counts, rows)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: Dict[str, jax.Array],
                         config: MimoConfig, prefix_lens: jax.Array,
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
    x = _embed(params, tokens)                               # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # (B, S)
    rows = jnp.arange(B)[:, None]
    offs = abs_pos % T
    keep = jnp.arange(S)[None, :] < (lengths - prefix_lens)[:, None]
    pages, rope = {}, {}
    for kind, bt in tables.items():
        # A position outside the kind's columns goes to the scratch page,
        # never a clamped real one.
        col = abs_pos // T - first[kind][:, None]
        width = bt.shape[1]
        pages[kind] = jnp.where(
            (col >= 0) & (col < width),
            bt[rows, jnp.clip(col, 0, width - 1)], 0)
        rope[kind] = rope_at(abs_pos, c.inv_freq(kind))

    def body(seg, x, k_pool, v_pool, layer, base):
        kind = seg.kind
        kv, bt = c.kv_heads(kind), tables[kind]
        h = _normed(x, layer["attn_norm"], c)
        q, k_new, v_new = _qkv(layer, h, c, kind, *rope[kind])
        # The gathers follow the scatter, so the chunk sees itself.
        k_pool = k_pool.at[base + pages[kind], offs].set(
            k_new.astype(k_pool.dtype))
        v_pool = v_pool.at[base + pages[kind], offs].set(
            v_new.astype(v_pool.dtype))
        with jax.named_scope(f"{kind}_gather"):
            keys = bt.shape[1] * T
            k_all = k_pool[base + bt].reshape(B, keys, kv, c.head_dim)
            v_all = v_pool[base + bt].reshape(B, keys, kv, c.v_head_dim)
        att = chunk_attention(
            q.transpose(0, 2, 1, 3), k_all.transpose(0, 2, 1, 3),
            v_all.transpose(0, 2, 1, 3), prefix_lens, first[kind] * T,
            c.softmax_scale, window=c.window if kind == WINDOW else None,
            sink=layer.get("sink"))
        x = x + _attn_out(layer, att.transpose(0, 2, 1, 3), c)
        x, stats = _ffn(layer, x, c, seg, keep)
        return x, k_pool, v_pool, stats

    x, pool, _ = _scan_segments(body, x, params, pool, c)
    idx = jnp.clip(lengths - prefix_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _head(params, x_last, c), pool


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: Dict[str, jax.Array], config: MimoConfig,
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
                      tokens: jax.Array, config: MimoConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array, jax.Array]:
    """One token per slot. ``tokens`` (B,) are written at ``lengths[b]``;
    ``view`` is ``live_page_view``'s pair.

    A full layer reads the listed pages a block of ``VIEW_BLOCK`` rows at a
    time, as many blocks as hold live rows, and scores every group of
    ``VIEW_GROUP`` pages against its owner's 64 queries in one matmul; the
    softmax is taken PER SLOT across its groups and blocks from the
    running maximum and sum (``deepseek_decode.paged_decode_step`` takes
    the same two statistics in one pass), a group's probabilities meet
    its values in one matmul and a slot's groups are added up. A window
    layer gathers its slot's ``R`` pages, masks what lies outside ``0 <= i
    - j < window`` and adds the sink to the
    denominator. A slot that owns no row writes to the scratch pages, is
    left out of the experts' pairs and gets finite junk logits. Returns
    ``(logits, pool, lengths + 1, stats)``."""
    c = config
    B = tokens.shape[0]
    T = pool["full_k"].shape[2]
    H, D, Dv = c.n_heads, c.head_dim, c.v_head_dim
    pages, owner, index = view[FULL][0], view[FULL][1], view[FULL][2]
    w_pages, w_index = view[WINDOW][0], view[WINDOW][1]      # (B, R)
    N, G, R = pages.shape[0], VIEW_GROUP, w_pages.shape[1]
    if N % G:
        raise ValueError(f"a view of {N} rows is not whole groups of {G}")
    pos = lengths
    x = _embed(params, tokens)[:, None]                      # (B, 1, E)
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
    rope = {kind: rope_at(pos[:, None], c.inv_freq(kind))
            for kind in (FULL, WINDOW)}
    valid = ((owner >= 0)[:, None]
             & (index[:, None] * T + jnp.arange(T)[None, :]
                <= pos[jnp.maximum(owner, 0)][:, None]))     # (N, T)
    valid = valid.reshape(N // G, 1, G * T)
    group_owner = owner.reshape(N // G, G)[:, 0]             # (N / G,)
    of_group = jnp.maximum(group_owner, 0)
    mine = group_owner[None, :] == jnp.arange(B)[:, None]    # (B, N / G)
    w_pos = (w_index[:, :, None] * T
             + jnp.arange(T)[None, None, :]).reshape(B, R * T)
    back = pos[:, None] - w_pos
    w_valid = ((jnp.repeat(w_index, T, axis=1) >= 0)
               & (back >= 0) & (back < c.window))[:, None, None, :]
    scale = c.softmax_scale
    high = jax.lax.Precision.HIGHEST

    # The full list is read in BLOCKS of whole groups, as many as hold
    # live rows (the list's real rows come first): the rung a program was
    # compiled for bounds the loop and no longer sets the work, which
    # follows the live pages to within a block (a step that needs 4,160
    # rows read the 8,192 of its rung before).
    block = min(N // G, VIEW_BLOCK // G)                     # groups
    live_blocks = -(-jnp.sum(group_owner >= 0) // block)

    def full_attend(q, k_pool, v_pool, base, sink):
        """``q`` (B, KV, Hg, D) over the listed pages of ``k_pool`` /
        ``v_pool`` (rows of ``(T, KV x D/Dv)``, a token's heads flat as
        they are cached). The gathered pages are read where they lie: a
        head's query is laid out over ALL key heads' lanes, zero but on
        its own (``KV`` times the operations, on 64 rows that are
        nothing), so a group's scores are one matmul against the flat
        rows, and the values' likewise with the own head's block of the
        result kept; a per-head view of the pages would be a transposed
        copy of them a layer. The softmax runs over the blocks: a slot's
        maximum, sum and weighted values are carried and rescaled."""
        kv = k_pool.shape[-1] // D
        hg = H // kv
        own = jnp.eye(kv, dtype=q.dtype)                     # (KV, KV)
        q_flat = jnp.einsum("bkhd,kj->bkhjd", q, own).reshape(
            B, H, kv * D)

        def one(i, carry):
            top, total, acc = carry           # (B, H), (B, H), (B, H, Dv)
            g0 = i * block
            rows = jax.lax.dynamic_slice_in_dim(pages, g0 * G, block * G)
            whose = jax.lax.dynamic_slice_in_dim(of_group, g0, block)
            seen = jax.lax.dynamic_slice_in_dim(valid, g0, block)
            part_of = jax.lax.dynamic_slice_in_dim(mine, g0, block, 1)
            with jax.named_scope("full_gather"):
                k = k_pool[base + rows].reshape(block, G * T, kv * D)
                v = v_pool[base + rows].reshape(block, G * T, kv * Dv)
            s = jnp.einsum("ghc,gtc->ght", q_flat[whose], k,
                           preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * scale, -1e30)        # (block, H, GT)
            new = jnp.maximum(top, jnp.max(jnp.where(
                part_of[:, :, None], s.max(-1)[None], -1e30), axis=1))
            e = jnp.where(seen, jnp.exp(s - new[whose][..., None]), 0.0)
            part = jnp.einsum("ght,gtc->ghc", e.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)
            part = jnp.einsum("gkhjd,kj->gkhd",
                              part.reshape(block, kv, hg, kv, Dv),
                              own.astype(jnp.float32), precision=high)
            # A 0/1 matrix at full precision adds a slot's groups up in
            # float32 and rounds nothing.
            adds = part_of.astype(jnp.float32)
            shrink = jnp.exp(top - new)
            total = total * shrink + jnp.einsum(
                "bg,gh->bh", adds, e.sum(-1), precision=high)
            acc = acc * shrink[..., None] + jnp.einsum(
                "bg,ghd->bhd", adds, part.reshape(block, H, Dv),
                precision=high)
            return new, total, acc

        # A sink is where the softmax starts: its logit the maximum so
        # far, 1 in the sum, no value.
        start = (jnp.full((B, H), -1e30, jnp.float32) if sink is None
                 else jnp.broadcast_to(sink[None, :], (B, H)))
        _, total, acc = jax.lax.fori_loop(
            0, live_blocks, one,
            (start, jnp.full((B, H), 0.0 if sink is None else 1.0),
             jnp.zeros((B, H, Dv), jnp.float32)))
        return acc / jnp.where(total > 0.0, total, 1.0)[..., None]

    def window_attend(q, k, v, sink):
        """``q`` (B, KV, Hg, D); ``k``/``v`` (B, R, T, KV x D/Dv)."""
        kv = k.shape[-1] // D
        k = k.reshape(B, R * T, kv, D)
        v = v.reshape(B, R * T, kv, Dv)
        s = jnp.einsum("bkhd,btkd->bkht", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(w_valid, s * scale, -1e30)
        top = s.max(-1)
        if sink is not None:
            top = jnp.maximum(top, sink.reshape(1, kv, -1))
        e = jnp.where(w_valid, jnp.exp(s - top[..., None]), 0.0)
        total = e.sum(-1)
        if sink is not None:
            total = total + jnp.exp(sink.reshape(1, kv, -1) - top)
        acc = jnp.einsum("bkht,btkd->bkhd", e.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return acc / jnp.where(total > 0.0, total, 1.0)[..., None]

    def body(seg, x, k_pool, v_pool, layer, base):
        kind = seg.kind
        kv = c.kv_heads(kind)
        h = _normed(x, layer["attn_norm"], c)
        q, k_new, v_new = _qkv(layer, h, c, kind, *rope[kind])
        k_pool = k_pool.at[base + write[kind], off].set(
            k_new[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[base + write[kind], off].set(
            v_new[:, 0].astype(v_pool.dtype))
        heads = q[:, 0].reshape(B, kv, H // kv, D)
        if kind == FULL:
            with jax.named_scope("full_attn"):
                att = full_attend(heads, k_pool, v_pool, base,
                                  layer.get("sink"))
        else:
            with jax.named_scope("window_gather"):
                k, v = k_pool[base + w_pages], v_pool[base + w_pages]
            with jax.named_scope("window_attn"):
                att = window_attend(heads, k, v, layer.get("sink"))
        x = x + _attn_out(layer, att.reshape(B, 1, H, Dv), c)
        x, stats = _ffn(layer, x, c, seg, steps[:, None])
        return x, k_pool, v_pool, stats

    x, pool, stats = _scan_segments(body, x, params, pool, c)
    return _head(params, x[:, 0], c), pool, pos + 1, stats
