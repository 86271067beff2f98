"""Autoregressive decoding for the Llama family: KV cache + jitted
prefill/decode steps + ``generate``.

The serving-side other half of ``models/llama.py`` (VERDICT r4 Missing #2;
reference: serving generation flows through the model-agnostic replica call
path ``python/ray/serve/_private/replica.py:231`` with streaming
``proxy.py:761`` — the reference has no model library, so its KV cache
lives in user code/vLLM; here it is TPU-native and first-class).

Design for the XLA/TPU execution model:

* **Static cache buckets**: the cache is a fixed ``(L, B, C, KV, D)``
  allocation (``C`` = a power-of-two-ish capacity bucket). One compiled
  program per (B, C) bucket, reused across requests forever — no dynamic
  shapes, no recompiles mid-stream.
* **Per-slot lengths**: every batch row carries its own ``length``;
  attention masks key positions ``>= length`` so right-padded prefills and
  continuously-batched decodes of different-length requests share one
  program (the continuous-batching primitive ``serve/decode.py`` builds
  on).
* **GQA-aware**: queries are grouped over KV heads
  (``(B, KV, G, D) x (B, C, KV, D)``) so grouped-query models never
  materialize repeated K/V — the cache stays at KV-head width, which is
  the whole point of GQA for decode bandwidth.
* **Decode is a masked dot per layer**: at ``S_q = 1`` attention is
  HBM-bandwidth-bound (read K/V once); a flash kernel cannot beat the
  plain masked dot XLA emits, so the Pallas path is reserved for prefill
  (``attention_impl="flash"`` with ``q_offset`` chunked prefill). What
  decides a step's cost is how much K/V the dot is given: the paged step
  reads the pages its slots hold (``live_page_view``), not every slot's
  whole window.

Two sets of forwards. ``init_cache`` / ``prefill`` / ``decode_step`` /
``generate`` over a contiguous per-row cache are the plain reference the
tests hold the engine to (``prefill`` is also the causal half of
``paged_prefill``). ``serve/decode.py`` runs the three over the paged
pool: ``paged_prefill``, ``paged_prefill_suffix``, ``paged_decode_step``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rotary import apply_rope, rope_frequencies
from ray_tpu.parallel.sharding import constrain

Cache = Dict[str, jax.Array]


def cache_bucket(n: int, minimum: int = 128) -> int:
    """Smallest power-of-two >= n (>= minimum): the shape buckets decode
    programs compile for."""
    c = minimum
    while c < n:
        c *= 2
    return c


def init_cache(config: LlamaConfig, batch: int, capacity: int,
               dtype=None) -> Cache:
    """Zeroed KV cache for ``batch`` slots of ``capacity`` tokens."""
    c = config
    if c.moe_experts:
        raise NotImplementedError(
            "KV-cache decode for MoE configs is not implemented yet "
            "(dense + GQA only)")
    dt = dtype or c.dtype
    shape = (c.n_layers, batch, capacity, c.n_kv_heads, c.head_dim)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _cast(w: jax.Array, dtype) -> jax.Array:
    """A weight in the compute dtype. The scope names the f32 -> bf16
    converts in a device trace (``weight_cast``); for weights already in
    ``dtype`` it is nothing at all."""
    with jax.named_scope("weight_cast"):
        return w.astype(dtype)


# The leaves the programs below read through ``_cast``, at the top of the
# tree and under ``layers`` (tests/test_engine_weights.py holds this list
# to the call sites). The norm scales are not among them: ``rms_norm``
# lifts those to float32 itself.
CAST_LEAVES = ("tok_embed", "lm_head")
CAST_LAYER_LEAVES = ("wq", "wk", "wv", "wqkv", "wo",
                     "w_gate", "w_up", "w_gate_up", "w_down")


def compute_weights(params: Dict[str, Any], config: LlamaConfig,
                    donate: bool = False) -> Dict[str, Any]:
    """``params`` with every leaf that ``_cast`` converts held in
    ``config.dtype``, so a program run on the result converts nothing: a
    serving replica never updates its weights, and rounds them once here
    instead of once a program call. A leaf already in that dtype is the
    same array and every other leaf is passed through, so float32 compute
    changes nothing. ``params`` and its arrays are left as they are,
    unless the caller owns them and says ``donate``: then each converted
    leaf's source is deleted as soon as its copy exists, and the
    transient is one leaf, not the tree."""
    dtype = jnp.dtype(config.dtype)

    def held(w):
        if w.dtype == dtype:
            return w
        out = jnp.asarray(w, dtype=dtype)
        if donate:
            # Wait for the copy: a delete behind an unfinished convert
            # frees nothing yet, and the next leaf's copy would stack up.
            out.block_until_ready()
            w.delete()
        return out

    def over(tree, names):
        return {k: held(v) if k in names else v for k, v in tree.items()}

    out = over(params, CAST_LEAVES)
    out["layers"] = over(params["layers"], CAST_LAYER_LEAVES)
    return out


def _paged_gather(k_p, v_p, block_tables, config: LlamaConfig):
    """Each row's pages back in logical order, ``(B, W * T, KV, D)``: the
    view the paged attention reads. A row is a request and ``W`` its
    window (the chunk), or one live page and ``W`` = 1 (the decode
    step's list, ``live_page_view``). Named for the device trace:
    one K and one V operation a layer, which is what the benchmark's
    roofline share counts."""
    B, W = block_tables.shape
    shape = (B, W * k_p.shape[1], config.n_kv_heads, config.head_dim)
    with jax.named_scope("paged_gather"):
        return (k_p[block_tables].reshape(shape),
                v_p[block_tables].reshape(shape))


def _scan_layers(body, x, params: Dict[str, Any], pool: Cache):
    """The layer loop of a paged forward, with the pool in its CARRY:
    ``body((x, k_pool, v_pool), (layer, base)) -> ((x, k_pool, v_pool),
    None)``. Returns ``(x, pool)``, the pool in the shape it came in.

    Inside the loop the pool is flat, ``(L * (P + 1), T, KV, D)``: layers
    and pages on one axis (a bitcast; neither axis is ever sharded), page
    ``p`` of layer ``l`` at row ``base + p`` with ``base = l * (P + 1)``.
    A layer's write and its gather so index the whole pool and no layer
    is sliced out of it, and XLA updates a loop carry where it lies: with
    the pool donated at the jit boundary, a program stores its few new
    rows into the caller's buffer. The pool must not ride the scan's
    ``xs``/``ys``: a stacked output cannot alias a scanned input, and
    every call then writes a new pool whole (tests/test_paged_kv.py
    guards the compiled text)."""
    shape = pool["k"].shape
    flat = (shape[0] * shape[1],) + shape[2:]
    bases = jnp.arange(shape[0], dtype=jnp.int32) * shape[1]
    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, pool["k"].reshape(flat), pool["v"].reshape(flat)),
        (params["layers"], bases))
    return x, {"k": k_pool.reshape(shape), "v": v_pool.reshape(shape)}


def _pool_store(k_pool, v_pool, base, pages, offs, k_new, v_new,
                block_tables, config: LlamaConfig):
    """One layer's KV traffic on the flat pool of ``_scan_layers``:
    scatter the new positions' K/V to ``(base + pages, offs)``, then
    gather the rows' pages, so that the new tokens are in view. Returns
    ``(k_pool, v_pool, k_view, v_view)``. The write stays outside the
    ``paged_gather`` scope: a trace bills it to neither gather nor
    attention."""
    k_pool = k_pool.at[base + pages, offs].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[base + pages, offs].set(v_new.astype(v_pool.dtype))
    k_c, v_c = _paged_gather(k_pool, v_pool, base + block_tables, config)
    return k_pool, v_pool, k_c, v_c


def _qkv(layer, h, config: LlamaConfig):
    c = config
    if "wqkv" in layer:
        qkv = jnp.einsum("bse,ehd->bshd", h, _cast(layer["wqkv"], h.dtype))
        return (qkv[:, :, :c.n_heads],
                qkv[:, :, c.n_heads:c.n_heads + c.n_kv_heads],
                qkv[:, :, c.n_heads + c.n_kv_heads:])
    q = jnp.einsum("bse,ehd->bshd", h, _cast(layer["wq"], h.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, _cast(layer["wk"], h.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, _cast(layer["wv"], h.dtype))
    return q, k, v


def _mlp(layer, x, config: LlamaConfig):
    h2 = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    if "w_gate_up" in layer:
        gate_up = jnp.einsum("bse,em->bsm", h2,
                             _cast(layer["w_gate_up"], h2.dtype))
        gate, up = jnp.split(gate_up, 2, axis=-1)
    else:
        gate = jnp.einsum("bse,em->bsm", h2,
                          _cast(layer["w_gate"], h2.dtype))
        up = jnp.einsum("bse,em->bsm", h2, _cast(layer["w_up"], h2.dtype))
    ffn = jax.nn.silu(gate) * up
    # Pre-contraction anchor (see llama._decoder_layer): under DECODE
    # rules this all-gathers the mlp-sharded hidden so the w_down
    # reduction is never split across the mesh (bit-exactness contract).
    ffn = constrain(ffn, ("batch", "length", "mlp_hidden"))
    down = jnp.einsum("bsm,me->bse", ffn, _cast(layer["w_down"], h2.dtype))
    return x + down


def prefill(params: Dict[str, Any], tokens: jax.Array, cache: Cache,
            config: LlamaConfig,
            lengths: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Cache]:
    """Process right-padded prompts (B, S), filling the cache.

    Returns ``(last_logits (B, V) fp32, cache)`` where ``last_logits`` is
    the next-token distribution at each row's final REAL token. Causality
    keeps real positions clean of the padding (padding sits to the right);
    the junk K/V the padded tail writes is masked by ``length`` at decode
    time. Prefill attention uses the config's impl ("flash" = the Pallas
    kernel with chunked ``q_offset``)."""
    from ray_tpu.models.llama import _decoder_layer

    c = config
    B, S = tokens.shape
    capacity = cache["k"].shape[2]
    if S > capacity:
        raise ValueError(f"prompt length {S} exceeds cache capacity "
                         f"{capacity}")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    x = _cast(params["tok_embed"], c.dtype)[tokens]
    cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)

    def body(x, layer):
        # Full-layer forward identical to training (shared code), but k/v
        # are recomputed here to feed the cache — cheap (two matmuls)
        # next to the layer itself, and keeps _decoder_layer signature
        # untouched for the train path.
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        _, k, v = _qkv(layer, h, c)
        k = apply_rope(k, cos, sin)
        k = constrain(k, ("batch", "length", "kv_heads", "head_dim"))
        v = constrain(v, ("batch", "length", "kv_heads", "head_dim"))
        x, _aux = _decoder_layer(c, x, layer, cos, sin, 0)
        return x, (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    # ks: (L, B, S, KV, D) -> cache[:, :, :S]
    new_k = jax.lax.dynamic_update_slice(cache["k"], ks, (0, 0, 0, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], vs, (0, 0, 0, 0, 0))
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = jnp.einsum("be,ev->bv", x_last,
                        _cast(params["lm_head"], c.dtype),
                        preferred_element_type=jnp.float32)
    return logits, {"k": new_k, "v": new_v, "length": lengths}


def decode_step(params: Dict[str, Any], cache: Cache, tokens: jax.Array,
                config: LlamaConfig) -> Tuple[jax.Array, Cache]:
    """Append one token per slot and return next-token logits.

    ``tokens``: (B,) int32 — each row's token is written at position
    ``cache["length"][row]``; attention sees positions ``<= length``.
    Jit with ``donate_argnums`` on the cache: the update is in-place on
    device (no (L,B,C,KV,D) copy per token)."""
    c = config
    B = tokens.shape[0]
    pos = cache["length"]  # (B,)
    cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)
    x = _cast(params["tok_embed"], c.dtype)[tokens][:, None]  # (B, 1, E)
    capacity = cache["k"].shape[2]
    kv_groups = c.n_heads // c.n_kv_heads
    scale = c.head_dim ** -0.5
    rows = jnp.arange(B)
    # Key positions 0..pos are valid (including the token being appended).
    valid = (jnp.arange(capacity)[None, :] <= pos[:, None])  # (B, C)

    def body(x, inp):
        layer, k_c, v_c = inp
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k_new, v_new = _qkv(layer, h, c)      # (B, 1, H/KV, D)
        q = apply_rope(q, cos, sin, positions=pos[:, None])
        k_new = apply_rope(k_new, cos, sin, positions=pos[:, None])
        q = constrain(q, ("batch", "length", "heads", "head_dim"))
        k_new = constrain(k_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        v_new = constrain(v_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        k_c = k_c.at[rows, pos].set(k_new[:, 0].astype(k_c.dtype))
        v_c = v_c.at[rows, pos].set(v_new[:, 0].astype(v_c.dtype))
        # GQA attention against the cache at KV-head width: q grouped as
        # (B, KV, G, D), scores (B, KV, G, C) — repeated K/V never exist.
        qg = q[:, 0].reshape(B, c.n_kv_heads, kv_groups, c.head_dim)
        scores = jnp.einsum("bkgd,bckd->bkgc", qg, k_c,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum("bkgc,bckd->bkgd", probs.astype(v_c.dtype), v_c)
        att = att.reshape(B, 1, c.n_heads, c.head_dim).astype(x.dtype)
        att = constrain(att, ("batch", "length", "attn_heads", "head_dim"))
        out = jnp.einsum("bshd,hde->bse", att, _cast(layer["wo"], x.dtype))
        x = x + out
        x = _mlp(layer, x, c)
        return x, (k_c, v_c)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("be,ev->bv", x[:, 0],
                        _cast(params["lm_head"], c.dtype),
                        preferred_element_type=jnp.float32)
    return logits, {"k": new_k, "v": new_v, "length": pos + 1}


# --------------------------------------------------------------- paged KV
#
# vLLM-style paged attention on XLA-friendly static shapes: K/V for ALL
# slots live in one device pool of ``(pages, page_tokens)`` blocks, and a
# per-slot block table (int32 page ids, static width) maps logical token
# positions to pool pages. The prefills gather a slot's pages back into
# logical order — value for value the layout of the reference's
# contiguous cache (``init_cache``), under the same masked-dot attention;
# the decode step gathers the flat list of the pages its slots hold and
# takes each slot's softmax across its pages (``live_page_view``).
#
# Page id 0 is a reserved scratch page: block-table entries for positions
# a slot never allocated point at it, so pad writes land somewhere
# harmless (never read — masking is by per-slot ``length``/causality).
# The host-side allocator
# (``serve/paging.py``) hands out ids 1..pages.


def init_page_pool(config: LlamaConfig, pages: int, page_tokens: int,
                   dtype=None) -> Cache:
    """Zeroed paged KV pool: ``pages`` usable pages of ``page_tokens``
    tokens each, plus the reserved scratch page 0 (so the arrays hold
    ``pages + 1`` page rows)."""
    c = config
    if c.moe_experts:
        raise NotImplementedError(
            "paged KV-cache decode for MoE configs is not implemented yet "
            "(dense + GQA only)")
    dt = dtype or c.dtype
    shape = (c.n_layers, pages + 1, page_tokens, c.n_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Cache,
                  block_tables: jax.Array, config: LlamaConfig,
                  lengths: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Cache]:
    """Full prefill of right-padded prompts (B, S) scattered into pool
    pages. The attention itself is the plain causal ``prefill`` (a fresh
    prompt attends only to itself — no pool reads); only the K/V
    destination differs: position ``p`` of row ``b`` lands in page
    ``block_tables[b, p // T]`` at offset ``p % T``.

    ``block_tables``: (B, W) int32 with ``W * T >= S``. Pad positions
    past a row's real length scatter into whatever page backs them —
    the row's own tail page or the scratch page 0 — and are never read
    (causally invisible at prefill, masked by ``length`` at decode)."""
    B, S = tokens.shape
    T = pool["k"].shape[2]
    scratch = {
        "k": jnp.zeros(pool["k"].shape[:1] + (B, S)
                       + pool["k"].shape[3:], pool["k"].dtype),
        "v": jnp.zeros(pool["v"].shape[:1] + (B, S)
                       + pool["v"].shape[3:], pool["v"].dtype),
        "length": jnp.zeros((B,), jnp.int32),
    }
    logits, scratch = prefill(params, tokens, scratch, config, lengths)
    pos = jnp.arange(S)
    pages = block_tables[:, pos // T]                    # (B, S)
    offs = jnp.broadcast_to(pos % T, (B, S))
    new_k = pool["k"].at[:, pages, offs].set(scratch["k"])
    new_v = pool["v"].at[:, pages, offs].set(scratch["v"])
    return logits, {"k": new_k, "v": new_v}


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Cache, block_tables: jax.Array,
                         config: LlamaConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Cache]:
    """Suffix prefill against paged context: process right-padded suffix
    ``tokens`` (B, S) from ``pos = prefix_lens``, attending to the pages
    ``block_tables`` (B, W) maps — the shared/previously-filled prefix
    pages plus the causal part of the suffix. This one program is the
    prefix-hit splice (prefix pages borrowed from the pool with ZERO
    copies — the block table entries ARE the splice) and the chunked-
    prefill continuation step (prefix = what earlier chunks wrote).

    ``W`` is a static page width covering ``prefix + suffix`` for the
    whole wave; pass block tables sliced to it so gather/attention cost
    scales with what the wave touches, not the engine's max context.
    Suffix K/V additionally scatters into the pool at the absolute
    positions (always pages owned exclusively by the row: sharing is
    full-page and writes start past the shared region). The pool rides
    the layer loop as its carry and is written in place
    (``_scan_layers``); it comes back in the shape it was given."""
    c = config
    B, S = tokens.shape
    T = pool["k"].shape[2]
    W = block_tables.shape[1]
    C = W * T
    cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)
    x = _cast(params["tok_embed"], c.dtype)[tokens]          # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # (B, S)
    kv_groups = c.n_heads // c.n_kv_heads
    scale = c.head_dim ** -0.5
    rows = jnp.arange(B)
    # Pad positions past the static page window (a bucket overhanging a
    # row's real length) scatter to the SCRATCH page, never a clamped
    # real page — an index clamp here would corrupt live K/V at the
    # pad's page offset.
    pages = jnp.where(
        abs_pos < C,
        block_tables[rows[:, None], jnp.minimum(abs_pos // T, W - 1)], 0)
    offs = abs_pos % T
    valid = (jnp.arange(C)[None, None, :]
             <= abs_pos[:, :, None])                         # (B, S, C)

    def body(carry, inp):
        x, k_p, v_p = carry                 # the flat pool: _scan_layers
        layer, base = inp
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k_new, v_new = _qkv(layer, h, c)  # (B, S, H/KV, D)
        q = apply_rope(q, cos, sin, positions=abs_pos)
        k_new = apply_rope(k_new, cos, sin, positions=abs_pos)
        q = constrain(q, ("batch", "length", "heads", "head_dim"))
        k_new = constrain(k_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        v_new = constrain(v_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        # The gather follows the scatter, so the suffix's own causal K/V
        # is in view; layout is logical position order.
        k_p, v_p, k_c, v_c = _pool_store(k_p, v_p, base, pages, offs,
                                         k_new, v_new, block_tables, c)
        with jax.named_scope("paged_attn"):
            qg = q.reshape(B, S, c.n_kv_heads, kv_groups, c.head_dim)
            scores = jnp.einsum("bskgd,bckd->bkgsc", qg, k_c,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(valid[:, None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            att = jnp.einsum("bkgsc,bckd->bkgsd", probs.astype(v_c.dtype), v_c)
        att = att.transpose(0, 3, 1, 2, 4).reshape(
            B, S, c.n_heads, c.head_dim).astype(x.dtype)
        att = constrain(att, ("batch", "length", "attn_heads", "head_dim"))
        out = jnp.einsum("bshd,hde->bse", att, _cast(layer["wo"], x.dtype))
        x = x + out
        x = _mlp(layer, x, c)
        return (x, k_p, v_p), None

    x, pool = _scan_layers(body, x, params, pool)
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    idx = jnp.clip(lengths - prefix_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = jnp.einsum("be,ev->bv", x_last,
                        _cast(params["lm_head"], c.dtype),
                        preferred_element_type=jnp.float32)
    return logits, pool


def live_page_view(block_tables, counts, rows: int):
    """The decode step's view of the pool, built on the host: a flat list
    of the pages that the stepping slots hold, ``(3, rows)`` int32 with a
    row ``(pool page, owning slot, index of the page in the slot's
    sequence)`` for each of the first ``counts[slot]`` entries of
    ``block_tables[slot]``, slot by slot. A slot that does not decode in
    this step (idle, mid-prefill) has count 0 and owns no row; a page
    that two slots share (a prefix hit) is a row of each. The rows past
    the list pad it to ``rows``, the static width the program was
    compiled for: the scratch page, owned by slot -1, which is nobody.

    So the step gathers and attends over what its contexts hold, rounded
    up to ``rows``, and not over ``slots x pages a slot may hold``."""
    tables = np.asarray(block_tables)
    counts = np.asarray(counts)
    slot, index = np.nonzero(
        np.arange(tables.shape[1])[None, :] < counts[:, None])
    n = len(slot)
    if n > rows:
        raise ValueError(f"{n} live pages do not fit a view of {rows}")
    view = np.zeros((3, rows), np.int32)
    view[1] = -1
    view[0, :n] = tables[slot, index]
    view[1, :n] = slot
    view[2, :n] = index
    return view


def paged_decode_step(params: Dict[str, Any], pool: Cache,
                      view: jax.Array, lengths: jax.Array,
                      tokens: jax.Array, config: LlamaConfig
                      ) -> Tuple[jax.Array, Cache, jax.Array]:
    """One decode token per slot against paged context. ``tokens``: (B,)
    int32 written at position ``lengths[b]`` of each slot's sequence;
    attention sees positions ``<= length`` across the slot's pages: the
    reference ``decode_step`` in another order of summation.

    ``view`` is ``live_page_view``'s ``(3, N)`` list of the pages the
    stepping slots hold. Each layer gathers those ``N`` pages (one K and
    one V gather, ``(N, T, KV, D)``), scores every row against its
    owner's query, and takes the softmax ACROSS the rows of one slot from
    the usual two statistics (the maximum and the sum, reduced over the
    slot's rows through the ``(B, N)`` membership mask), so the work
    follows the sum of the contexts and not ``B`` times the longest.
    Scores, statistics and the sum of a slot's partial outputs are
    float32; probabilities are rounded to the pool's dtype before the
    value product, as the reference rounds them.

    A slot that owns no row (idle, mid-prefill: static ``B``) writes its
    token's K/V to the scratch page and gets finite junk logits. The pool
    rides the layer loop as its carry (``_scan_layers``): each layer
    scatters its ``B`` new rows into the donated buffer, and the program
    holds no second pool."""
    c = config
    B = tokens.shape[0]
    T = pool["k"].shape[2]
    pages, owner, index = view[0], view[1], view[2]          # (N,) each
    pos = lengths                                            # (B,)
    cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)
    x = _cast(params["tok_embed"], c.dtype)[tokens][:, None]  # (B, 1, E)
    kv_groups = c.n_heads // c.n_kv_heads
    scale = c.head_dim ** -0.5
    member = owner[None, :] == jnp.arange(B)[:, None]        # (B, N)
    # The page a slot writes is its row at index pos // T. Without such a
    # row (the slot does not step, or its cursor is parked past its
    # pages) the sum is 0, the scratch page: never a live one.
    page = jnp.sum(jnp.where(
        member & (index[None, :] == (pos // T)[:, None]),
        pages[None, :], 0), axis=1)
    off = pos % T
    # A pad row reads slot 0's query and cursor; ``valid`` masks it whole.
    of_row = jnp.maximum(owner, 0)                           # (N,)
    valid = ((owner >= 0)[:, None]
             & (index[:, None] * T + jnp.arange(T)[None, :]
                <= pos[of_row][:, None]))                    # (N, T)
    member_f32 = member.astype(jnp.float32)

    def body(carry, inp):
        x, k_p, v_p = carry                 # the flat pool: _scan_layers
        layer, base = inp
        h = rms_norm(x, layer["attn_norm"], c.norm_eps)
        q, k_new, v_new = _qkv(layer, h, c)       # (B, 1, H/KV, D)
        q = apply_rope(q, cos, sin, positions=pos[:, None])
        k_new = apply_rope(k_new, cos, sin, positions=pos[:, None])
        q = constrain(q, ("batch", "length", "heads", "head_dim"))
        k_new = constrain(k_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        v_new = constrain(v_new,
                          ("batch", "length", "kv_heads", "head_dim"))
        k_p, v_p, k_c, v_c = _pool_store(k_p, v_p, base, page, off,
                                         k_new[:, 0], v_new[:, 0],
                                         pages[:, None], c)  # (N, T, KV, D)
        with jax.named_scope("paged_attn"):
            qg = q[:, 0].reshape(B, c.n_kv_heads, kv_groups, c.head_dim)
            scores = jnp.einsum("nkgd,ntkd->nkgt", qg[of_row], k_c,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(valid[:, None, None, :], scores, -1e30)
            # The two statistics of a slot's softmax, over its rows.
            top = jnp.max(jnp.where(member[:, :, None, None],
                                    scores.max(-1)[None], -1e30), axis=1)
            e = jnp.where(valid[:, None, None, :],
                          jnp.exp(scores - top[of_row][..., None]), 0.0)
            total = jnp.sum(jnp.where(member[:, :, None, None],
                                      e.sum(-1)[None], 0.0), axis=1)
            total = jnp.where(total > 0.0, total, 1.0)   # a slot of no rows
            probs = e / total[of_row][..., None]             # (N, KV, G, T)
            part = jnp.einsum("nkgt,ntkd->nkgd", probs.astype(v_c.dtype),
                              v_c, preferred_element_type=jnp.float32)
            # A one-hot matrix at full precision adds a slot's rows up in
            # float32 and rounds nothing.
            att = jnp.einsum("bn,nkgd->bkgd", member_f32, part,
                             precision=jax.lax.Precision.HIGHEST)
        att = att.reshape(B, 1, c.n_heads, c.head_dim).astype(x.dtype)
        att = constrain(att, ("batch", "length", "attn_heads", "head_dim"))
        out = jnp.einsum("bshd,hde->bse", att, _cast(layer["wo"], x.dtype))
        x = x + out
        x = _mlp(layer, x, c)
        return (x, k_p, v_p), None

    x, pool = _scan_layers(body, x, params, pool)
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("be,ev->bv", x[:, 0],
                        _cast(params["lm_head"], c.dtype),
                        preferred_element_type=jnp.float32)
    return logits, pool, pos + 1


# ------------------------------------------------- GSPMD serving (mesh)
#
# One replica spanning a pod (sub-)slice instead of one chip: weights,
# KV state and activations carry NamedShardings over the named 2-D
# ``decode_mesh`` (("batch", "model")) and every program above is jitted
# with in/out shardings — XLA inserts the collectives (no hand-rolled
# ring/all-reduce anywhere in the serve plane). The sharding rules
# (``parallel.sharding.DECODE_RULES``) never partition a contraction
# dim: model size scales with the "model" axis (HBM per chip drops),
# slot count with the "batch" axis, and sharded logits are the
# single-chip programs' within a float tolerance
# (tests/test_sharded_decode.py).


def decode_shardings(config: LlamaConfig, mesh) -> Dict[str, Any]:
    """Sharding bundle for a decode replica on ``mesh`` (a
    ``parallel.mesh.decode_mesh``): NamedShardings for the params pytree,
    the paged KV pool and host-facing (replicated) outputs, plus the
    resolved rule table.

    ``pool["length"]`` stays replicated: it is a few bytes, every
    decode step scatters it at a traced slot index, and the host reads
    it back for admission accounting."""
    from jax.sharding import NamedSharding, PartitionSpec
    from ray_tpu.models.llama import decode_param_axes
    from ray_tpu.parallel.sharding import (decode_rules, spec_for,
                                           tree_shardings)

    rules = decode_rules(config, mesh)

    def ns(*axes):
        return NamedSharding(mesh, spec_for(axes, rules))

    pool_row = ("layers", None, None, "kv_heads", "head_dim")
    return {
        "rules": rules,
        "params": tree_shardings(mesh, decode_param_axes(config), rules),
        "pool": {"k": ns(*pool_row), "v": ns(*pool_row),
                 "length": NamedSharding(mesh, PartitionSpec())},
        "replicated": NamedSharding(mesh, PartitionSpec()),
    }


def shard_decode_state(params: Dict[str, Any], config: LlamaConfig,
                       mesh) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Device-put ``params`` onto ``mesh`` with the decode shardings.
    Returns ``(sharded_params, shardings_bundle)`` — the engine commits
    the weights once at construction; the jitted programs inherit the
    committed input shardings and pin their outputs with the bundle."""
    shardings = decode_shardings(config, mesh)
    return jax.device_put(params, shardings["params"]), shardings


def _sample(logits: jax.Array, temperature: float, key) -> jax.Array:
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def sample_batch(logits: jax.Array, temperatures: jax.Array,
                 key) -> jax.Array:
    """The sample every engine program ends in: per row the greedy
    argmax where ``temperatures[b] <= 0`` (the first maximum, as
    ``np.argmax`` takes it), else a draw from ``softmax(logits / T)`` at
    that row's temperature on the stream ``key`` starts. A batch with no
    temperature, which is most of them, draws nothing: the noise over
    the whole vocabulary is made only in the branch that uses it."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        temps = jnp.maximum(temperatures, 1e-6)[:, None]
        sampled = jax.random.categorical(
            key, logits / temps, axis=-1).astype(jnp.int32)
        return jnp.where(temperatures <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temperatures > 0.0), draw, lambda: greedy)


@partial(jax.jit, static_argnames=("config", "max_new_tokens",
                                   "temperature", "eos_id"))
def _generate_jit(params, tokens, lengths, key, config: LlamaConfig,
                  max_new_tokens: int, temperature: float,
                  eos_id: int):
    B, S = tokens.shape
    capacity = cache_bucket(S + max_new_tokens)
    cache = init_cache(config, B, capacity)
    logits, cache = prefill(params, tokens, cache, config, lengths)
    key, sub = jax.random.split(key)
    first = _sample(logits, temperature, sub)
    done0 = (first == eos_id) if eos_id >= 0 else jnp.zeros(B, bool)

    def step(carry, _):
        cache, tok, key, done = carry
        logits, cache = decode_step(params, cache, tok, config)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, temperature, sub)
        nxt = jnp.where(done, eos_id if eos_id >= 0 else 0, nxt)
        done = done | ((nxt == eos_id) if eos_id >= 0 else False)
        return (cache, nxt, key, done), nxt

    (_, _, _, _), rest = jax.lax.scan(
        step, (cache, first, key, done0), None,
        length=max_new_tokens - 1)
    return jnp.concatenate([first[None], rest], axis=0).T  # (B, max_new)


def generate(params: Dict[str, Any], tokens, config: LlamaConfig,
             max_new_tokens: int = 32, temperature: float = 0.0,
             key=None, eos_id: Optional[int] = None,
             lengths=None) -> jax.Array:
    """Generate ``max_new_tokens`` per prompt row as ONE jitted program
    (``prefill`` + scanned ``decode_step`` over an ``init_cache`` row):
    the plain single-sequence reference that the tests hold engine
    streams to. No engine calls it: ``serve/decode.py`` serves through
    the paged programs above, so requests can join/leave the batch
    between steps."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if tokens.ndim == 1:
        tokens = tokens[None]
    if key is None:
        key = jax.random.key(0)
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    return _generate_jit(params, tokens, lengths, key, config,
                         int(max_new_tokens), float(temperature),
                         -1 if eos_id is None else int(eos_id))
