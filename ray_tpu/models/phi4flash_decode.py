"""The programs ``serve/decode.py`` runs for Phi-4-mini-flash
(``phi4flash.py``): what the engine asks of a model module (docs/SERVING.md,
"The model seam"), over a pool of two KINDS of page and a STATE a slot.

* ``full_k`` / ``full_v`` ``[1, pages + 1, T, kv_width]``: the keys and
  values of the ONE full layer, which the cross-attention layers read too;
* ``window_k`` / ``window_v`` ``[window layers, window pages + 1, T,
  kv_width]``: a window layer is read no further back than ``window`` - 1
  tokens (``page_kinds``; the engine frees the pages behind);
* ``ssm`` ``[Mamba layers, slots + 1, d_state, d_inner]`` float32 and
  ``conv`` ``[Mamba layers, slots + 1, d_conv - 1, d_inner]``: a Mamba
  layer's state and the last inputs of its convolution, a SLOT each
  (``slot_state``); row ``slots`` is scratch, where pad rows write.

A token's heads lie FLAT on a page's last axis, pair after pair and in a
pair ``[k1 | k2]``; a key-value pair's value is the 128 numbers ``[v1 | v2]``
where they lie.

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``): a chunk runs
  the Mamba and window layers and the full layer's key-value projection at
  every position, from the state the last chunk left (zero where the row
  starts at position 0), and leaves its own. The full layer's queries, the
  layers behind it, the final norm and the head run at a row's LAST
  position and only in a program some row of which ends its prompt
  (``block_tables["ends"]``): nothing reads them anywhere else.
* **decode** (``paged_decode_step``): one token a slot through every
  layer. Both kinds' pages are read where they lie, by the kernel that
  scores them (``ops/paged_decode_attention.py``): a window layer hands it
  one list of ``keep`` pages a slot, the eight readers of the full kind
  ``moe_decode``'s groups of one slot's pages; no program copies pages
  first. A slot that owns no row of the view (idle, or between two prefill
  chunks) keeps its state bit for bit.
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
from ray_tpu.models.moe_decode import VIEW_GROUP, view_rows  # noqa: F401
from ray_tpu.models.phi4flash import FLOAT32_LEAVES, Phi4FlashConfig
from ray_tpu.ops.chunk_attention import chunk_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                paged_decode_attention)
from ray_tpu.ops.selective_scan import selective_scan, selective_step

Pool = Dict[str, jax.Array]
FULL, WINDOW = "full", "window"

# The most tokens (rows x bucket) the engine gives one prefill program: a
# wave's temporaries (the scan's float32 inputs and outputs, 60 KB a token a
# layer) have to fit beside a pool that fills the chip.
PREFILL_TOKENS_MAX = 4096


def page_kinds(config: Phi4FlashConfig) -> Dict[str, Dict[str, Any]]:
    return {FULL: {"window": None, "leaves": ("full_k", "full_v")},
            WINDOW: {"window": config.window,
                     "leaves": ("window_k", "window_v")}}


def slot_state(config: Phi4FlashConfig) -> Tuple[str, ...]:
    """The pool's leaves that are indexed by SLOT and not by page."""
    return ("ssm", "conv")


def compute_weights(params: Dict[str, Any], config: Phi4FlashConfig,
                    donate: bool = False) -> Dict[str, Any]:
    return moe_decode.cast_weights(params, config.dtype, FLOAT32_LEAVES,
                                   donate)


def init_page_pool(config: Phi4FlashConfig, pages: Dict[str, int],
                   page_tokens: int, dtype=None, slots: int = 0) -> Pool:
    """Zeroed pool: for each kind ``pages[kind]`` usable pages and the
    scratch page 0; for each Mamba layer ``slots`` states and the scratch
    row behind them."""
    c = config
    dtype = dtype or c.dtype
    mamba = c.front_pairs + 1
    pool = {}
    for kind, layers in ((FULL, 1), (WINDOW, c.front_pairs)):
        for part in "kv":
            pool[f"{kind}_{part}"] = jnp.zeros(
                (layers, pages[kind] + 1, page_tokens, c.kv_width), dtype)
    pool["ssm"] = jnp.zeros((mamba, slots + 1, c.d_state, c.d_inner),
                            jnp.float32)
    pool["conv"] = jnp.zeros((mamba, slots + 1, c.d_conv - 1, c.d_inner),
                             dtype)
    return pool


# ------------------------------------------------------------ layer pieces
#
# Every piece takes the residual stream with any leading axes (B, S, E in a
# prefill, B, E in a decode step); it is float32 through the layers and the
# matmuls read ``_ln``'s copy in the compute dtype.


def _ln(x, w, b, c: Phi4FlashConfig):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + c.norm_eps) * w
            + b).astype(c.dtype)


def _mlp(layer, x, c: Phi4FlashConfig):
    h = _ln(x, layer["ln2_w"], layer["ln2_b"], c)
    gu = jnp.einsum("...e,ef->...f", h, layer["w1"])
    ffn = jax.nn.silu(gu[..., :c.mlp_dim]) * gu[..., c.mlp_dim:]
    return x + jnp.einsum("...f,fe->...e", ffn, layer["w2"])


def _mamba_inputs(layer, xc, c: Phi4FlashConfig):
    """From the convolved input ``xc`` (..., Di) float32: ``dt`` (..., Di),
    ``Bm`` and ``C`` (..., N), float32."""
    r, n = c.dt_rank, c.d_state
    proj = jnp.einsum("...d,dr->...r", xc.astype(c.dtype), layer["x_proj"],
                      preferred_element_type=jnp.float32)
    dt = jnp.einsum("...r,rd->...d", proj[..., :r].astype(c.dtype),
                    layer["dt_proj"], preferred_element_type=jnp.float32)
    dt = jax.nn.softplus(dt + layer["dt_bias"])
    return dt, proj[..., r:r + n], proj[..., r + n:]


def _mamba_out(layer, y, z, c: Phi4FlashConfig):
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(c.dtype)
    return jnp.einsum("...d,de->...e", gated, layer["out_proj"])


def _mamba_seq(layer, x, c: Phi4FlashConfig, ssm, conv, n_real):
    """A chunk through a Mamba layer: ``x`` (B, S, E) the stream, ``ssm``
    (B, N, Di) and ``conv`` (B, K - 1, Di) the state before its first
    position, ``n_real`` (B,) its real positions. Returns ``(x, y, ssm,
    conv)``: ``y`` (B, S, Di) the scan's output before the gate."""
    S, di, taps = x.shape[1], c.d_inner, c.d_conv
    h = _ln(x, layer["ln1_w"], layer["ln1_b"], c)
    xz = jnp.einsum("bse,ef->bsf", h, layer["in_proj"])
    xs, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("ssm_conv"):
        xin = jnp.concatenate([conv.astype(xs.dtype), xs], axis=1)
        acc = layer["conv_b"][None, None]
        for k in range(taps):
            acc = acc + layer["conv_w"][k].astype(jnp.float32) \
                * xin[:, k:k + S].astype(jnp.float32)
        xc = jax.nn.silu(acc)
        # The tail: the last K - 1 REAL inputs (a row with none keeps its
        # old tail, which is ``xin``'s head).
        tail = n_real[:, None] + jnp.arange(taps - 1)[None, :]
        conv = jnp.take_along_axis(xin, tail[:, :, None], axis=1)
    dt, bm, cm = _mamba_inputs(layer, xc, c)
    with jax.named_scope("ssm_scan"):
        y, ssm = selective_scan(
            xc, dt, -jnp.exp(layer["A_log"]), bm, cm, layer["D"], ssm,
            lengths=n_real)
    x = _mlp(layer, x + _mamba_out(layer, y, z, c), c)
    return x, y, ssm, conv.astype(c.dtype)


def _mamba_one(layer, x, c: Phi4FlashConfig, ssm, conv, at, steps):
    """One token a slot through Mamba layer ``at``: ``x`` (B, E); ``ssm``
    and ``conv`` the pool's state leaves, whose rows ``[at, :B]`` are read
    and written where they lie; a slot outside ``steps`` (B,) keeps both
    as they are. Returns ``(x, y, ssm, conv)``."""
    B, di = x.shape[0], c.d_inner
    h = _ln(x, layer["ln1_w"], layer["ln1_b"], c)
    xz = jnp.einsum("be,ef->bf", h, layer["in_proj"])
    xs, z = xz[:, :di], xz[:, di:]
    with jax.named_scope("ssm_step"):
        s0 = jax.lax.dynamic_index_in_dim(ssm, at, 0, False)[:B]
        c0 = jax.lax.dynamic_index_in_dim(conv, at, 0, False)[:B]
        xin = jnp.concatenate([c0.astype(xs.dtype), xs[:, None]], axis=1)
        xc = jax.nn.silu(layer["conv_b"][None] + jnp.sum(
            layer["conv_w"].astype(jnp.float32)[None]
            * xin.astype(jnp.float32), axis=1))
        dt, bm, cm = _mamba_inputs(layer, xc, c)
        y, s1 = selective_step(xc, dt, -jnp.exp(layer["A_log"]), bm, cm,
                               layer["D"], s0)
        s1 = jnp.where(steps[:, None, None], s1, s0)
        c1 = jnp.where(steps[:, None, None], xin[:, 1:].astype(c0.dtype), c0)
        ssm = jax.lax.dynamic_update_slice(ssm, s1[None], (at, 0, 0, 0))
        conv = jax.lax.dynamic_update_slice(conv, c1[None], (at, 0, 0, 0))
    x = _mlp(layer, x + _mamba_out(layer, y, z, c), c)
    return x, y, ssm, conv


def _gmu(layer, x, m, c: Phi4FlashConfig):
    """``x += W_out(silu(W_in LN(x)) * m)``, ``m`` (..., Di) float32 the
    middle Mamba layer's scan output at the same position."""
    with jax.named_scope("gmu"):
        h = _ln(x, layer["ln1_w"], layer["ln1_b"], c)
        g = jnp.einsum("...e,ed->...d", h, layer["in_proj"])
        gated = (jax.nn.silu(g.astype(jnp.float32)) * m).astype(c.dtype)
        x = x + jnp.einsum("...d,de->...e", gated, layer["out_proj"])
    return _mlp(layer, x, c)


def _project(layer, x, c: Phi4FlashConfig, first: int = 0,
             last: Optional[int] = None):
    """Columns ``first:last`` of the fused projection of ``LN(x)``, with
    their bias: the queries are the first ``dim`` of them, the keys and the
    values ``kv_width`` each behind."""
    h = _ln(x, layer["ln1_w"], layer["ln1_b"], c)
    return jnp.einsum("...e,ef->...f", h, layer["wqkv"][:, first:last]) \
        + layer["bqkv"][first:last].astype(c.dtype)


def _lam(layer, lam0):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` of a layer's four
    vectors."""
    lam = layer["lam"]
    return jnp.exp(jnp.sum(lam[0] * lam[1])) \
        - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam0


def _diff_out(layer, a, lam0, x, c: Phi4FlashConfig):
    """The differential form: ``a`` (..., H, 2 D) float32 holds, head by
    head (pair ``i``'s two heads side by side), ``softmax(q k) [v1 | v2]``;
    the pair's output is ``RMSNorm(a1 - lam a2) (1 - lam0)``. Adds the
    block's output to the stream ``x``."""
    a = a.reshape(a.shape[:-2] + (c.q_pairs, 2, a.shape[-1]))
    o = a[..., 0, :] - _lam(layer, lam0) * a[..., 1, :]
    o = rms_norm(o, layer["subln"], c.norm_eps) * (1.0 - lam0)
    o = o.reshape(o.shape[:-2] + (c.dim,)).astype(c.dtype)
    return x + jnp.einsum("...f,fe->...e", o, layer["o_proj"]) \
        + layer["o_bias"].astype(c.dtype)


def _head_maps(c: Phi4FlashConfig):
    """Query head ``h`` = (pair ``i``, half ``s``) reads key head ``(i //
    g) * 2 + s`` and the value pair ``i // g``: ``own`` (H, KV) and
    ``pair`` (H, KV / 2), 0/1."""
    i, s = np.arange(c.n_heads) // 2, np.arange(c.n_heads) % 2
    j = i // (c.q_pairs // c.kv_pairs)
    own = np.zeros((c.n_heads, c.n_kv_heads), np.float32)
    own[np.arange(c.n_heads), j * 2 + s] = 1.0
    pair = np.zeros((c.n_heads, c.kv_pairs), np.float32)
    pair[np.arange(c.n_heads), j] = 1.0
    return own, pair


def _flat_queries(q, c: Phi4FlashConfig):
    """``q`` (..., H x D) -> (..., H, KV x D): each head's query laid out
    over ALL key heads' lanes, zero but on its own, so that a score is one
    matmul against the keys as they are cached, flat
    (``mimo_decode.full_attend``: KV times the operations, on rows that are
    nothing; a per-head view of the pages would be a transposed copy)."""
    own, _ = _head_maps(c)
    q = q.reshape(q.shape[:-1] + (c.n_heads, c.head_dim))
    flat = jnp.einsum("...hd,hk->...hkd", q, jnp.asarray(own, q.dtype))
    return flat.reshape(flat.shape[:-2] + (c.kv_width,))


def _own_values(part, c: Phi4FlashConfig):
    """(..., H, KV x D) weighted values over all lanes -> (..., H, 2 D):
    each head's own pair ``[v1 | v2]``."""
    _, pair = _head_maps(c)
    part = part.reshape(part.shape[:-1] + (c.kv_pairs, 2 * c.head_dim))
    return jnp.einsum("...hjd,hj->...hd", part, jnp.asarray(pair),
                      precision=jax.lax.Precision.HIGHEST)


def _attend_rows(q, k, v, seen, c: Phi4FlashConfig):
    """Each row's queries over its own keys: ``q`` (B, H x D), ``k`` / ``v``
    (B, C, KV x D) flat as cached, ``seen`` (B, C) bool. Returns (B, H, 2
    D) float32. A row that sees nothing gets zeros."""
    s = jnp.einsum("bhc,btc->bht", _flat_queries(q, c), k,
                   preferred_element_type=jnp.float32) * c.softmax_scale
    s = jnp.where(seen[:, None, :], s, -1e30)
    e = jnp.where(seen[:, None, :],
                  jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    total = e.sum(-1)
    part = jnp.einsum("bht,btc->bhc", e.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
    return _own_values(part, c) / jnp.where(total > 0.0, total,
                                            1.0)[..., None]


def _head(params, x, c: Phi4FlashConfig):
    """(B, E) -> float32 logits (B, V) through the tied embedding."""
    x = _ln(x, params["final_ln_w"], params["final_ln_b"], c)
    return jnp.einsum("be,ve->bv", x, params["tok_embed"],
                      preferred_element_type=jnp.float32)


def _lam0s(c: Phi4FlashConfig, layers) -> jax.Array:
    return jnp.asarray([c.lam0(l) for l in layers], jnp.float32)


def _flat(pool: Pool):
    """The page leaves with layers and pages on one axis (page ``p`` of
    layer ``l`` at row ``l x (pages + 1) + p``), and their shapes."""
    shapes = {n: pool[n].shape for n in pool if n[-2:] in ("_k", "_v")}
    return {n: pool[n].reshape((-1,) + s[2:])
            for n, s in shapes.items()}, shapes


def _pool(pool: Pool, flat, shapes, full_k, full_v, ssm, conv) -> Pool:
    """The pool a program hands back: ``_flat``'s leaves in their shapes
    again, the full kind's as written, the state as left."""
    flat = {**flat, "full_k": full_k, "full_v": full_v}
    return {**pool, "ssm": ssm, "conv": conv,
            **{name: leaf.reshape(shapes[name])
               for name, leaf in flat.items()}}


# ------------------------------------------------------- the decode's view


def live_page_view(block_tables: Dict[str, Any], counts: Dict[str, Any],
                   rows: Dict[str, int]) -> Dict[str, np.ndarray]:
    """The decode step's view of both kinds, built on the host
    (``mimo_decode.live_page_view``): ``"full"`` a slot's pages in whole
    groups of ``VIEW_GROUP`` on ``rows["full"]`` rows, ``"window"`` the
    ``rows["window"]`` last window pages of each stepping slot."""
    return moe_decode.kinds_page_view(block_tables, counts, rows)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: Dict[str, jax.Array],
                         config: Phi4FlashConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Pool]:
    """Right-padded ``tokens`` (B, S) from ``pos = prefix_lens``: the
    chunked-prefill continuation and (from 0) the whole prefill.
    ``block_tables`` maps ``"full"`` (B, W) the row's leading full pages,
    ``"window"`` (B, Ww) its window pages from the sequence's page
    ``"window_first"`` (B,) on, ``"slots"`` (B,) the row's slot (a pad row
    names the scratch row) and ``"ends"`` (B,) whether the row's prompt
    ends here. A row at ``prefix_lens`` 0 starts from a zero state. Returns
    the logits at the last real token of the rows that end (zeros where no
    row does) and the pool."""
    c = config
    B, S = tokens.shape
    T = pool["full_k"].shape[2]
    bt, wt = block_tables[FULL], block_tables[WINDOW]
    w_first = block_tables["window_first"].astype(jnp.int32)
    slots, ends = block_tables["slots"], block_tables["ends"]
    x = params["tok_embed"][tokens].astype(jnp.float32)      # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # (B, S)
    rows = jnp.arange(B)[:, None]
    offs = abs_pos % T
    n_real = lengths - prefix_lens
    fresh = (prefix_lens == 0)[:, None, None]

    def pages_of(table, first):
        # A position outside the columns goes to the scratch page, never a
        # clamped real one.
        col = abs_pos // T - first[:, None]
        width = table.shape[1]
        return jnp.where((col >= 0) & (col < width),
                         table[rows, jnp.clip(col, 0, width - 1)], 0)

    w_pages = pages_of(wt, w_first)
    f_pages = pages_of(bt, jnp.zeros((B,), jnp.int32))
    flat, shapes = _flat(pool)

    def mamba(layer, at, x, ssm, conv):
        s0 = jnp.where(fresh, 0.0, ssm[at, slots])
        c0 = jnp.where(fresh, 0, conv[at, slots])
        x, y, s1, c1 = _mamba_seq(layer, x, c, s0, c0, n_real)
        return x, y, ssm.at[at, slots].set(s1), conv.at[at, slots].set(c1)

    def front(carry, inp):
        x, wk, wv, ssm, conv = carry
        m_layer, w_layer, at, lam0 = inp
        x, _, ssm, conv = mamba(m_layer, at, x, ssm, conv)
        base = at * shapes["window_k"][1]
        qkv = _project(w_layer, x, c)
        k_new = qkv[..., c.dim:c.dim + c.kv_width]
        v_new = qkv[..., c.dim + c.kv_width:]
        # The gathers follow the scatter, so the chunk sees itself.
        wk = wk.at[base + w_pages, offs].set(k_new.astype(wk.dtype))
        wv = wv.at[base + w_pages, offs].set(v_new.astype(wv.dtype))
        with jax.named_scope("window_gather"):
            keys = wt.shape[1] * T
            k_all = wk[base + wt].reshape(B, keys, c.n_kv_heads, c.head_dim)
            v_all = wv[base + wt].reshape(B, keys, c.kv_pairs,
                                          2 * c.head_dim)
        # ``chunk_attention``'s head ``h`` reads key head ``h // 2``: the
        # queries go in by key head (pair ``j``, half ``s``), the query
        # pairs of the key pair side by side, and a key head's value is
        # its pair's ``[v1 | v2]``.
        g = c.q_pairs // c.kv_pairs
        q = qkv[..., :c.dim].reshape(B, S, c.kv_pairs, g, 2, c.head_dim)
        q = q.transpose(0, 2, 4, 3, 1, 5).reshape(B, c.n_heads, S,
                                                  c.head_dim)
        with jax.named_scope("window_attn"):
            att = chunk_attention(
                q, k_all.transpose(0, 2, 1, 3),
                jnp.repeat(v_all.transpose(0, 2, 1, 3), 2, axis=1),
                prefix_lens, w_first * T, c.softmax_scale, window=c.window)
        att = att.reshape(B, c.kv_pairs, 2, g, S, 2 * c.head_dim)
        att = att.transpose(0, 4, 1, 3, 2, 5).reshape(
            B, S, c.n_heads, 2 * c.head_dim).astype(jnp.float32)
        x = _mlp(w_layer, _diff_out(w_layer, att, lam0, x, c), c)
        return (x, wk, wv, ssm, conv), None

    n = c.front_pairs
    (x, flat["window_k"], flat["window_v"], ssm, conv), _ = jax.lax.scan(
        front, (x, flat["window_k"], flat["window_v"], pool["ssm"],
                pool["conv"]),
        (params["mamba"], params["window"], jnp.arange(n, dtype=jnp.int32),
         _lam0s(c, range(1, c.half, 2))))
    x, m, ssm, conv = mamba(params["mamba_mid"], n, x, ssm, conv)
    # The full layer's keys and values at every position: THE cache.
    full = params["full"]
    kv = _project(full, x, c, first=c.dim)
    fk = flat["full_k"].at[f_pages, offs].set(
        kv[..., :c.kv_width].astype(flat["full_k"].dtype))
    fv = flat["full_v"].at[f_pages, offs].set(
        kv[..., c.kv_width:].astype(flat["full_v"].dtype))
    idx = jnp.clip(n_real - 1, 0, S - 1)[:, None, None].astype(jnp.int32)

    def cross_stack():
        """The full layer's queries and everything behind them, at each
        row's last real position."""
        x1 = jnp.take_along_axis(x, idx, axis=1)[:, 0]          # (B, E)
        m1 = jnp.take_along_axis(m, idx, axis=1)[:, 0]          # (B, Di)
        with jax.named_scope("full_gather"):
            k_all = fk[bt].reshape(B, bt.shape[1] * T, c.kv_width)
            v_all = fv[bt].reshape(B, bt.shape[1] * T, c.kv_width)
        seen = jnp.arange(bt.shape[1] * T)[None, :] < lengths[:, None]

        def attend(layer, q, lam0, x1, scope):
            with jax.named_scope(scope):
                att = _attend_rows(q, k_all, v_all, seen, c)
            return _mlp(layer, _diff_out(layer, att, lam0, x1, c), c)

        x1 = attend(full, _project(full, x1, c, last=c.dim),
                    c.lam0(c.half + 1), x1, "full_attn")

        def back(x1, inp):
            g_layer, c_layer, lam0 = inp
            x1 = _gmu(g_layer, x1, m1, c)
            return attend(c_layer, _project(c_layer, x1, c), lam0, x1,
                          "cross_attn"), None

        x1, _ = jax.lax.scan(back, x1, (
            params["gmu"], params["cross"],
            _lam0s(c, range(c.half + 3, c.n_layers, 2))))
        return _head(params, x1, c)

    logits = jax.lax.cond(
        jnp.any(ends), cross_stack,
        lambda: jnp.zeros((B, params["tok_embed"].shape[0]), jnp.float32))
    return logits, _pool(pool, flat, shapes, fk, fv, ssm, conv)


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: Dict[str, jax.Array],
                  config: Phi4FlashConfig,
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
                      tokens: jax.Array, config: Phi4FlashConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array]:
    """One token per slot. ``tokens`` (B,) are written at ``lengths[b]``;
    ``view`` is ``live_page_view``'s pair. A slot that owns no row of the
    full kind's list does not step: its keys and values go to the scratch
    pages, its state stays bit for bit as it is and its logits are finite
    junk. Returns ``(logits, pool, lengths + 1)``."""
    c = config
    B = tokens.shape[0]
    T = pool["full_k"].shape[2]
    pages, owner, index = view[FULL][0], view[FULL][1], view[FULL][2]
    w_pages, w_index = view[WINDOW][0], view[WINDOW][1]      # (B, R)
    N, G = pages.shape[0], VIEW_GROUP
    if N % G:
        raise ValueError(f"a view of {N} rows is not whole groups of {G}")
    pos = lengths
    x = params["tok_embed"][tokens].astype(jnp.float32)      # (B, E)
    member = owner[None, :] == jnp.arange(B)[:, None]        # (B, N)
    steps = member.any(axis=1)                               # (B,)
    off = pos % T
    # The page a slot writes, a kind: the one at index pos // T among its
    # rows, else the scratch page.
    f_write = jnp.sum(jnp.where(
        member & (index[None, :] == (pos // T)[:, None]),
        pages[None, :], 0), axis=1)
    w_write = jnp.sum(jnp.where(w_index == (pos // T)[:, None],
                                w_pages, 0), axis=1)
    flat, shapes = _flat(pool)
    # Both kinds' pages as the kernel's lists, built once for every layer
    # that reads them: a window layer one list a slot, the readers of the
    # full kind ``moe_decode``'s groups of one slot's pages.
    w_lists = page_lists(w_pages, jnp.arange(B, dtype=jnp.int32), w_index,
                         pos, T, c.window)
    f_lists = page_lists(pages.reshape(N // G, G),
                         owner.reshape(N // G, G)[:, 0],
                         index.reshape(N // G, G), pos, T)

    def attend(q, k_pool, v_pool, lists, first_page=0):
        """``q`` (B, H x D) over each slot's lists of pages, read where
        they lie in ``k_pool`` / ``v_pool`` (``paged_decode_attention``).
        Returns (B, H, 2 D) float32; a slot that sees nothing gets
        zeros."""
        _, total, part = paged_decode_attention(
            _flat_queries(q, c), k_pool, v_pool, lists, c.softmax_scale,
            first_page)
        total = total[..., None]
        return jnp.where(total > 0.0, _own_values(part, c) / total, 0.0)

    def front(carry, inp):
        x, wk, wv, ssm, conv = carry
        m_layer, w_layer, at, lam0 = inp
        x, _, ssm, conv = _mamba_one(m_layer, x, c, ssm, conv, at, steps)
        base = at * shapes["window_k"][1]
        qkv = _project(w_layer, x, c)
        wk = wk.at[base + w_write, off].set(
            qkv[:, c.dim:c.dim + c.kv_width].astype(wk.dtype))
        wv = wv.at[base + w_write, off].set(
            qkv[:, c.dim + c.kv_width:].astype(wv.dtype))
        with jax.named_scope("window_attn"):
            att = attend(qkv[:, :c.dim], wk, wv, w_lists, base)
        x = _mlp(w_layer, _diff_out(w_layer, att, lam0, x, c), c)
        return (x, wk, wv, ssm, conv), None

    n = c.front_pairs
    (x, flat["window_k"], flat["window_v"], ssm, conv), _ = jax.lax.scan(
        front, (x, flat["window_k"], flat["window_v"], pool["ssm"],
                pool["conv"]),
        (params["mamba"], params["window"], jnp.arange(n, dtype=jnp.int32),
         _lam0s(c, range(1, c.half, 2))))
    x, m, ssm, conv = _mamba_one(params["mamba_mid"], x, c, ssm, conv, n,
                                 steps)

    # ---- the full layer writes THE cache, eight layers read it ---------
    full = params["full"]
    qkv = _project(full, x, c)
    fk = flat["full_k"].at[f_write, off].set(
        qkv[:, c.dim:c.dim + c.kv_width].astype(flat["full_k"].dtype))
    fv = flat["full_v"].at[f_write, off].set(
        qkv[:, c.dim + c.kv_width:].astype(flat["full_v"].dtype))
    with jax.named_scope("full_attn"):
        att = attend(qkv[:, :c.dim], fk, fv, f_lists)
    x = _mlp(full, _diff_out(full, att, c.lam0(c.half + 1), x, c), c)

    def back_pair(x, inp):
        g_layer, c_layer, lam0 = inp
        x = _gmu(g_layer, x, m, c)
        with jax.named_scope("cross_attn"):
            att = attend(_project(c_layer, x, c), fk, fv, f_lists)
        return _mlp(c_layer, _diff_out(c_layer, att, lam0, x, c), c), None

    x, _ = jax.lax.scan(back_pair, x, (
        params["gmu"], params["cross"],
        _lam0s(c, range(c.half + 3, c.n_layers, 2))))
    return (_head(params, x, c),
            _pool(pool, flat, shapes, fk, fv, ssm, conv), pos + 1)
