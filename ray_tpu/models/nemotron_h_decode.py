"""The programs ``serve/decode.py`` runs for Nemotron-H (``nemotron_h.py``):
what the engine asks of a model module (docs/SERVING.md, "The model seam"),
over a pool of ONE kind of page beside a STATE a slot.

* ``full_k`` / ``full_v`` ``[attention layers, pages + 1, T, kv_width]``:
  the keys and values of the ``*`` layers, 2 heads of 128 flat on a page's
  last axis (``page_kinds``: one kind, which keeps every token);
* ``ssm`` ``[M layers, slots + 1, heads, head_dim, state]`` float32 and
  ``conv`` ``[M layers, slots + 1, d_conv - 1, conv_dim]``: a Mamba-2
  layer's state and the last inputs of its convolution, a SLOT each
  (``slot_state``); row ``slots`` is scratch, where pad rows write;
* nothing for the ``E`` layers.

Every layer is ONE part and one residual add. The layers ride one ``scan``
a segment (``moe_decode.scan_segments``: a run of one letter of the
pattern) with what the segment's kind keeps of the pool in the carry.

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``): a chunk runs
  every layer at every position: a Mamba-2 layer from the state the last
  chunk left (zero where the row starts at position 0) through
  ``ops/ssd.py::ssd_chunk`` and leaves its own; an attention layer writes
  its keys and values through the row's pages and attends through
  ``ops/chunk_attention.py``; an expert layer routes in float32 and runs the
  held experts on the LATENT projection (``ops/moe.py::held_experts_ffn``).
* **decode** (``paged_decode_step``): one token a slot. A Mamba-2 layer's
  state tile is read, advanced and written where it lies
  (``ssd.ssd_step``); the attention layer's pages are read where they lie
  (``ops/paged_decode_attention.py``, ``moe_decode``'s groups of one slot's
  pages). A slot that owns no row of the view (idle, or between two prefill
  chunks) keeps its state bit for bit, writes its keys to the scratch page
  and is left out of the experts' pairs.

The engine's optional program (``shard_decode_state``) is not here: the
engine refuses a mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
# Shared with every model the engine runs, and part of what this module
# provides: the prefill buckets and the fused sampler.
from ray_tpu.models.llama_decode import (cache_bucket,  # noqa: F401
                                         sample_batch)
# The view in groups of one slot's pages, and the rows it needs (the
# engine asks for ``view_rows``).
from ray_tpu.models.moe_decode import (VIEW_GROUP, live_page_view,  # noqa: F401
                                       view_rows)
from ray_tpu.models.nemotron_h import (FLOAT32_LEAVES, FULL, MAMBA,
                                       NemotronHConfig)
from ray_tpu.ops import moe
from ray_tpu.ops.chunk_attention import chunk_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                paged_decode_attention)
from ray_tpu.ops.ssd import ssd_chunk, ssd_step
from ray_tpu.parallel.sharding import constrain

Pool = Dict[str, jax.Array]

# What ``paged_decode_step`` counts beside its logits, summed over the
# expert layers, under ``deepseek_decode``'s names.
STEP_STATS = moe_decode.MOE_STEP_STATS

# The most tokens (rows x bucket) the engine gives one prefill program: a
# wave's temporaries (a Mamba-2 layer's float32 projections and outputs,
# ~76 KB a token) have to fit beside a pool that fills the chip.
PREFILL_TOKENS_MAX = 4096


def page_kinds(config: NemotronHConfig) -> Dict[str, Dict[str, Any]]:
    return {FULL: {"window": None, "leaves": ("full_k", "full_v")}}


def slot_state(config: NemotronHConfig) -> Tuple[str, ...]:
    """The pool's leaves that are indexed by SLOT and not by page."""
    return ("ssm", "conv")


def compute_weights(params: Dict[str, Any], config: NemotronHConfig,
                    donate: bool = False) -> Dict[str, Any]:
    return moe_decode.cast_weights(params, config.dtype, FLOAT32_LEAVES,
                                   donate)


def init_page_pool(config: NemotronHConfig, pages: int, page_tokens: int,
                   dtype=None, slots: int = 0) -> Pool:
    """Zeroed pool: ``pages`` usable pages and the scratch page 0 for each
    attention layer; for each Mamba-2 layer ``slots`` states and the
    scratch row behind them."""
    c = config
    dtype = dtype or c.dtype
    m = c.kind_layers(MAMBA)
    pool = {f"full_{part}": jnp.zeros(
        (c.kind_layers(FULL), pages + 1, page_tokens, c.kv_width), dtype)
        for part in "kv"}
    pool["ssm"] = jnp.zeros((m, slots + 1, c.mamba_heads, c.mamba_head_dim,
                             c.ssm_state), jnp.float32)
    pool["conv"] = jnp.zeros((m, slots + 1, c.d_conv - 1, c.conv_dim), dtype)
    return pool


# ------------------------------------------------------------ layer pieces
#
# Every piece takes the residual stream (B, S, E) in the model's dtype (a
# decode step is S = 1) and returns the PART, which its caller adds.


def _normed(layer, x, c: NemotronHConfig):
    """``RMSNorm(x)`` in float32: the router reads it before it is
    rounded, the matmuls its copy in the compute dtype."""
    return rms_norm(x.astype(jnp.float32), layer["norm"], c.norm_eps)


def _mamba_in(layer, x, c: NemotronHConfig, conv):
    """The Mamba-2 layer up to its state: ``x`` (B, S, E), ``conv`` (B, K -
    1, conv_dim) the inputs before the first position. Returns ``(z, xs,
    dt, Bm, Cm, xin)``: the gate (B, S, Di), the heads' inputs (B, S, H,
    P), the steps (B, S, H) float32, ``B`` and ``C`` (B, S, G, N), and the
    convolution's inputs with ``conv`` in front (B, K - 1 + S, conv_dim)."""
    B, S, _ = x.shape
    di, cd, taps = c.d_inner, c.conv_dim, c.d_conv
    g, n = c.ssm_groups, c.ssm_state
    h = _normed(layer, x, c).astype(c.dtype)
    zxd = jnp.einsum("bse,ef->bsf", h, layer["in_proj"])
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    xin = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
    acc = layer["conv_b"][None, None]
    for k in range(taps):
        acc = acc + layer["conv_w"][k].astype(jnp.float32) \
            * xin[:, k:k + S].astype(jnp.float32)
    xbc = jax.nn.silu(acc).astype(c.dtype)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    return (z, xbc[..., :di].reshape(B, S, c.mamba_heads, c.mamba_head_dim),
            dt, xbc[..., di:di + g * n].reshape(B, S, g, n),
            xbc[..., di + g * n:].reshape(B, S, g, n), xin)


def _mamba_out(layer, y, z, c: NemotronHConfig):
    """``y`` (B, S, H, P) float32 through the gate, the group norm (the
    gate BEFORE it; RMS inside each group's channels) and ``W_out``."""
    B, S = y.shape[:2]
    y = y.reshape(B, S, c.d_inner) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(B, S, c.ssm_groups, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + c.norm_eps)
    y = (yg.reshape(B, S, c.d_inner) * layer["gnorm"]).astype(c.dtype)
    return jnp.einsum("bsd,de->bse", y, layer["out_proj"])


def _qkv(layer, x, c: NemotronHConfig):
    """``q`` (B, S, H, D) and the rows that are cached, ``k`` and ``v`` (B,
    S, KV x D), heads flat. No positional term."""
    B, S, _ = x.shape
    h = _normed(layer, x, c).astype(c.dtype)
    q = jnp.einsum("bse,ef->bsf", h, layer["wq"])
    kv = jnp.einsum("bse,ef->bsf", h, layer["wkv"])
    return (q.reshape(B, S, c.n_heads, c.head_dim), kv[..., :c.kv_width],
            kv[..., c.kv_width:])


def _attn_out(layer, att, c: NemotronHConfig):
    """``att`` (B, S, H, D) -> the layer's part (B, S, E)."""
    # The pre-contraction anchors of ``llama_decode`` (no-ops without a
    # mesh, which this model has no rules for): no contraction is split.
    att = constrain(att.astype(c.dtype),
                    ("batch", "length", "attn_heads", "head_dim"))
    return jnp.einsum("bshd,hde->bse", att, layer["wo"])


def _experts(layer, x, c: NemotronHConfig, keep):
    """The expert layer's part on ``x`` (B, S, E): the held experts' share
    of the routed sum, in the latent width between ``W_1`` and ``W_2``,
    plus the shared expert on the full width. ``keep`` (B, S) bool: tokens
    that are real. Returns ``(part, stats)``."""
    B, S, E = x.shape
    normed = _normed(layer, x, c)
    h = normed.astype(c.dtype)
    router = c.router()
    with jax.named_scope("moe_router"):
        # float32, on the norm before it is rounded.
        logits = jnp.einsum("te,en->tn", normed.reshape(-1, E),
                            layer["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, weights = moe.route(logits, router, bias=layer["bias"])
    with jax.named_scope("moe_latent"):
        latent = jnp.einsum("bse,el->bsl", h, layer["w1"])
    with jax.named_scope("moe_experts"):
        routed, sizes = moe.held_experts_ffn(
            latent.reshape(-1, c.latent), idx, weights, layer["experts"],
            c.held, keep.reshape(-1), layer=layer["expert_layer"],
            router=router)
    with jax.named_scope("moe_latent"):
        routed = jnp.einsum("bsl,le->bse", routed.reshape(B, S, c.latent),
                            layer["w2"])
    with jax.named_scope("moe_shared"):
        up = jnp.einsum("bse,em->bsm", h, layer["shared"]["w_up"])
        ffn = constrain(jnp.square(jax.nn.relu(up)),
                        ("batch", "length", "mlp_hidden"))
        shared = jnp.einsum("bsm,me->bse", ffn, layer["shared"]["w_down"])
    return routed + shared, moe_decode.moe_step_stats(sizes)


def _head(params, x, c: NemotronHConfig):
    """(B, E) -> float32 logits (B, V): ``norm_f`` and the untied head."""
    with jax.named_scope("head"):
        x = rms_norm(x.astype(jnp.float32), params["final_norm"],
                     c.norm_eps).astype(c.dtype)
        return jnp.einsum("be,ev->bv", x, params["head"],
                          preferred_element_type=jnp.float32)


def _no_stats():
    """What a layer without experts adds to a step's counters."""
    return jnp.zeros((len(STEP_STATS),), jnp.float32)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: Dict[str, jax.Array],
                         config: NemotronHConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Pool]:
    """Right-padded ``tokens`` (B, S) from ``pos = prefix_lens``: the
    chunked-prefill continuation and (from 0) the whole prefill.
    ``block_tables`` maps ``"full"`` (B, W) the row's leading pages and
    ``"slots"`` (B,) the row's slot (a pad row names the scratch row). A
    row at ``prefix_lens`` 0 starts from a zero state. Returns the logits
    at each row's last real token and the pool."""
    c = config
    B, S = tokens.shape
    T = pool["full_k"].shape[2]
    bt, slots = block_tables[FULL], block_tables["slots"]
    x = params["tok_embed"][tokens].astype(c.dtype)            # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]    # (B, S)
    rows = jnp.arange(B)[:, None]
    offs = abs_pos % T
    n_real = lengths - prefix_lens
    keep = jnp.arange(S)[None, :] < n_real[:, None]
    fresh = prefix_lens == 0
    # A position outside the columns goes to the scratch page, never a
    # clamped real one.
    col, width = abs_pos // T, bt.shape[1]
    pages = jnp.where(col < width, bt[rows, jnp.clip(col, 0, width - 1)], 0)
    no_offset = jnp.zeros((B,), jnp.int32)
    taps = jnp.arange(c.d_conv - 1)[None, :]

    def body(seg, x, a_pool, b_pool, layer, base):
        if seg.kind == MAMBA:
            ssm, conv = a_pool, b_pool
            with jax.named_scope("ssd_proj"):
                c0 = jnp.where(fresh[:, None, None], 0, conv[base + slots])
                z, xs, dt, bm, cm, xin = _mamba_in(layer, x, c, c0)
                # The tail: the last K - 1 REAL inputs (a row with none
                # keeps its old tail, which is ``xin``'s head).
                tail = jnp.take_along_axis(
                    xin, (n_real[:, None] + taps)[:, :, None], axis=1)
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           ssm[base + slots])
            y, s1 = ssd_chunk(xs, dt, -jnp.exp(layer["A_log"]), bm, cm,
                              layer["D"], s0, n_real, c.chunk_size)
            with jax.named_scope("ssd_proj"):
                part = _mamba_out(layer, y, z, c)
            return (x + part, ssm.at[base + slots].set(s1),
                    conv.at[base + slots].set(tail.astype(conv.dtype)),
                    _no_stats())
        if seg.kind == FULL:
            k_pool, v_pool = a_pool, b_pool
            with jax.named_scope("attn_proj"):
                q, k_new, v_new = _qkv(layer, x, c)
            # The gathers follow the scatter, so the chunk sees itself.
            k_pool = k_pool.at[base + pages, offs].set(
                k_new.astype(k_pool.dtype))
            v_pool = v_pool.at[base + pages, offs].set(
                v_new.astype(v_pool.dtype))
            with jax.named_scope("full_gather"):
                keys = width * T
                k_all = k_pool[base + bt].reshape(B, keys, c.n_kv_heads,
                                                  c.head_dim)
                v_all = v_pool[base + bt].reshape(B, keys, c.n_kv_heads,
                                                  c.head_dim)
            att = chunk_attention(
                q.transpose(0, 2, 1, 3), k_all.transpose(0, 2, 1, 3),
                v_all.transpose(0, 2, 1, 3), prefix_lens, no_offset,
                c.softmax_scale)
            with jax.named_scope("attn_proj"):
                part = _attn_out(layer, att.transpose(0, 2, 1, 3), c)
            return x + part, k_pool, v_pool, _no_stats()
        part, stats = _experts(layer, x, c, keep)
        return x + part, a_pool, b_pool, stats

    x, pool, _ = moe_decode.scan_segments(body, x, c.segments(),
                                          params["segments"], pool)
    idx = jnp.clip(n_real - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _head(params, x_last, c), pool


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: Dict[str, jax.Array],
                  config: NemotronHConfig,
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


def paged_decode_step(params: Dict[str, Any], pool: Pool, view: jax.Array,
                      lengths: jax.Array, tokens: jax.Array,
                      config: NemotronHConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array, jax.Array]:
    """One token per slot. ``tokens`` (B,) are written at ``lengths[b]``;
    ``view`` is ``live_page_view``'s list, whole groups of ``VIEW_GROUP``
    pages of one slot. A slot that owns no row of it does not step: its
    keys and values go to the scratch page, its state stays bit for bit as
    it is, it is left out of the experts' pairs and its logits are finite
    junk. Returns ``(logits, pool, lengths + 1, stats)``."""
    c = config
    B = tokens.shape[0]
    T = pool["full_k"].shape[2]
    pages, owner, index = view[0], view[1], view[2]
    N, G = pages.shape[0], VIEW_GROUP
    if N % G:
        raise ValueError(f"a view of {N} rows is not whole groups of {G}")
    pos = lengths
    x = params["tok_embed"][tokens].astype(c.dtype)[:, None]   # (B, 1, E)
    member = owner[None, :] == jnp.arange(B)[:, None]          # (B, N)
    steps = member.any(axis=1)                                 # (B,)
    off = pos % T
    # The page a slot writes: the one at index pos // T among its rows,
    # else the scratch page.
    write = jnp.sum(jnp.where(
        member & (index[None, :] == (pos // T)[:, None]), pages[None, :],
        0), axis=1)
    lists = page_lists(pages.reshape(N // G, G),
                       owner.reshape(N // G, G)[:, 0],
                       index.reshape(N // G, G), pos, T)
    heads = (c.n_kv_heads, c.n_heads, c.head_dim)
    scratch = pool["ssm"].shape[1] - 1
    state_rows = jnp.where(steps, jnp.arange(B, dtype=jnp.int32), scratch)

    def body(seg, x, a_pool, b_pool, layer, base):
        if seg.kind == MAMBA:
            ssm, conv = a_pool, b_pool
            with jax.named_scope("ssd_proj"):
                c0 = jax.lax.dynamic_slice_in_dim(conv, base, B, 0)
                z, xs, dt, bm, cm, xin = _mamba_in(layer, x, c, c0)
                c1 = jnp.where(steps[:, None, None],
                               xin[:, 1:].astype(conv.dtype), c0)
                conv = jax.lax.dynamic_update_slice_in_dim(conv, c1, base, 0)
            y, ssm = ssd_step(xs[:, 0], dt[:, 0], -jnp.exp(layer["A_log"]),
                              bm[:, 0], cm[:, 0], layer["D"], ssm,
                              base + state_rows, steps)
            with jax.named_scope("ssd_proj"):
                part = _mamba_out(layer, y[:, None], z, c)
            return x + part, ssm, conv, _no_stats()
        if seg.kind == FULL:
            k_pool, v_pool = a_pool, b_pool
            with jax.named_scope("attn_proj"):
                q, k_new, v_new = _qkv(layer, x, c)
            k_pool = k_pool.at[base + write, off].set(
                k_new[:, 0].astype(k_pool.dtype))
            v_pool = v_pool.at[base + write, off].set(
                v_new[:, 0].astype(v_pool.dtype))
            with jax.named_scope("paged_attn"):
                _, total, part = paged_decode_attention(
                    moe_decode.flat_queries(q[:, 0], *heads).astype(
                        k_pool.dtype), k_pool, v_pool, lists,
                    c.softmax_scale, base)
                total = total[..., None]
                att = jnp.where(
                    total > 0.0,
                    moe_decode.own_values(part, *heads) / total, 0.0)
            with jax.named_scope("attn_proj"):
                part = _attn_out(layer, att[:, None], c)
            return x + part, k_pool, v_pool, _no_stats()
        part, stats = _experts(layer, x, c, steps[:, None])
        return x + part, a_pool, b_pool, stats

    x, pool, stats = moe_decode.scan_segments(body, x, c.segments(),
                                              params["segments"], pool)
    return _head(params, x[:, 0], c), pool, pos + 1, stats
