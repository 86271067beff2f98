"""The programs ``serve/decode.py`` runs for DeepSeek-V2 (``deepseek.py``):
what the engine asks of a model module (docs/SERVING.md, "The model
seam"), over a LATENT paged pool.

What is cached a token a layer is one row of ``kv_lora_rank +
qk_rope_head_dim`` numbers (512 + 64): the compressed key-value ``c_kv``
after its norm and the rotary key ``k_pe`` after its rotation, which all
128 heads share. The pool is ``{"latent": [layers, pages + 1, T, 640]}``,
a row being those 576 numbers and zeros up to the device's 128-wide tile
(``DeepseekConfig.latent_row``: the memory is the same, and the pool can
be written in place); as K and V per head the same tokens would take 57
times the room. Pages, block tables and the scratch page 0 are those of
``llama_decode`` (a page is a page: the allocator and the prefix index
never look inside one); the decode step's list of live pages is laid out
in groups of one slot's pages (``moe_decode.live_page_view``).

One attention, two formulations of the same mathematics:

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``) up-projects
  the gathered latents to per-head K and V with ``W_UKV``, the cheaper form
  per score when there are many queries, and hands them to the Pallas
  kernel ``ops/latent_attention.py``: a running softmax over tiles of
  queries and keys, so no program holds the scores at all (whole, 128
  heads x 2,048 x 16,384 float32 would be 17 GB), which skips the tiles
  above a row's causal frontier.
* **decode** (``paged_decode_step``) absorbs ``W_UK`` into the query and
  ``W_UV`` into the output and attends over the latent rows themselves,
  WHERE THEY LIE: ``score = (W_UK^T q_nope) . c_kv + q_pe . k_pe``, ``o_h
  = W_UV,h (P c_kv)``. The view lists a slot's pages in whole groups of
  ``VIEW_GROUP``, which are the lists ``ops/paged_decode_attention.py``
  takes: its one-pool form is handed the flat latent leaf with the
  layer's offset and a slot's 128 absorbed queries over all 640 lanes of a
  row, copies each live page once into VMEM, scores the row and weighs its
  first ``kv_lora_rank`` lanes, and adds a slot's groups up. No program
  copies the view's pages out of the pool (before PR 56 every layer did,
  335 MB at the served size).

The layers ride two ``scan``s (the leading dense ones, then the expert
ones) with the flat pool in the carry, so every program writes its new
rows into the donated buffer and none holds a second pool (PR 32).
The engine's optional program (``shard_decode_state``) is not here: the
engine refuses a mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.deepseek import NORM_LEAVES, DeepseekConfig
# Shared with ``mimo_decode``, and part of what this module provides: the
# decode's view in groups of one slot's pages (``live_page_view``,
# ``view_rows``), the cast of the weights, the expert layers' counters.
from ray_tpu.models.moe_decode import (MOE_STEP_STATS,  # noqa: F401
                                       VIEW_GROUP, cast_weights,
                                       live_page_view, moe_step_stats,
                                       view_rows)
# Shared with every model the engine runs, and part of what this module
# provides: the prefill buckets and the fused sampler.
from ray_tpu.models.llama_decode import (cache_bucket,  # noqa: F401
                                         sample_batch)
from ray_tpu.ops import moe
from ray_tpu.ops.latent_attention import latent_prefill_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_decode_attention import (page_lists,
                                                paged_decode_attention)
from ray_tpu.ops.rotary import rope_at, rotate_pairs
from ray_tpu.parallel.sharding import constrain

Pool = Dict[str, jax.Array]

# What ``paged_decode_step`` counts beside its logits, summed over the
# expert layers (the step log's ``launch`` slice carries them).
STEP_STATS = MOE_STEP_STATS


def compute_weights(params: Dict[str, Any], config: DeepseekConfig,
                    donate: bool = False) -> Dict[str, Any]:
    """``params`` with every matrix in ``config.dtype`` (the norm scales
    stay float32). ``deepseek.init_params`` already makes them so, and a
    leaf in that dtype is passed through; ``donate`` deletes a converted
    leaf's source as soon as its copy exists."""
    return cast_weights(params, config.dtype, NORM_LEAVES, donate)


def init_page_pool(config: DeepseekConfig, pages: int, page_tokens: int,
                   dtype=None) -> Pool:
    """Zeroed latent pool: ``pages`` usable pages and the scratch page 0,
    one row of ``latent_row`` numbers a token a layer (``latent_dim`` of
    them used)."""
    c = config
    return {"latent": jnp.zeros(
        (c.n_layers, pages + 1, page_tokens, c.latent_row),
        dtype or c.dtype)}


# ------------------------------------------------------------ layer pieces


def _embed(params, tokens):
    """The residual stream starts here and stays float32 through the
    layers: every block's output is ADDED to it, and in bfloat16 those
    ten adds round a stream of RMS ~2 by 2^-9 each, which is most of the
    distance between the replica's hidden state and the float32
    reference's, and so most of the tokens whose 6th and 7th expert swap
    places. The matmuls read ``_normed``'s compute-dtype copy."""
    return params["tok_embed"][tokens].astype(jnp.float32)


def _normed(x, scale, c: DeepseekConfig):
    """RMSNorm of the float32 stream, in the compute dtype."""
    return rms_norm(x, scale, c.norm_eps).astype(c.dtype)


def _queries(layer, h, c: DeepseekConfig):
    """``h`` (B, S, E) -> ``q_nope`` (B, S, H, nope), ``q_pe`` (B, S, H,
    rope) before its rotation."""
    cq = rms_norm(jnp.einsum("bse,er->bsr", h, layer["q_a"]),
                  layer["q_norm"], c.norm_eps)
    q = jnp.einsum("bsr,rhd->bshd", cq, layer["q_b"])
    return q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]


def _latent(layer, h, c: DeepseekConfig, cos, sin):
    """The row that is cached for each token of ``h`` (B, S, E):
    ``c_kv`` after its norm, ``k_pe`` after its rotation, zeros up to
    ``latent_row``."""
    ckv = jnp.einsum("bse,er->bsr", h, layer["kv_a"])
    c_kv = rms_norm(ckv[..., :c.kv_lora_rank], layer["kv_norm"],
                    c.norm_eps)
    k_pe = rotate_pairs(ckv[..., c.kv_lora_rank:], cos, sin,
                        interleaved=True)
    pad = jnp.zeros(h.shape[:-1] + (c.latent_row - c.latent_dim,), h.dtype)
    return jnp.concatenate([c_kv, k_pe.astype(h.dtype), pad], -1)


def _swiglu(w, x):
    """``x`` (B, S, E) through one SwiGLU."""
    gate = jnp.einsum("bse,em->bsm", x, w["w_gate"])
    up = jnp.einsum("bse,em->bsm", x, w["w_up"])
    # The pre-contraction anchors of ``llama_decode`` (a no-op without a
    # mesh, which this model has no rules for yet): under decode rules
    # the hidden is gathered, so that no contraction is ever split.
    ffn = constrain(jax.nn.silu(gate) * up,
                    ("batch", "length", "mlp_hidden"))
    return jnp.einsum("bsm,me->bse", ffn, w["w_down"])


def _dense_ffn(layer, x, c: DeepseekConfig):
    return x + _swiglu(layer, _normed(x, layer["mlp_norm"], c))


def _moe_ffn(layer, x, c: DeepseekConfig, keep):
    """Routed experts (the held ones' part) + the shared experts.
    ``keep`` (B, S) bool: tokens that are real. Returns ``(x, sizes)``,
    ``sizes`` the pairs each held expert computed."""
    shape = x.shape
    normed = rms_norm(x, layer["mlp_norm"], c.norm_eps)      # float32
    flat = normed.astype(c.dtype).reshape(-1, shape[-1])
    with jax.named_scope("moe_route"):
        # The published gate is float32 (``hidden_states.type(float32)``):
        # the router reads the stream before it is rounded for the
        # matmuls, at full precision. 160 outputs: 20 GFLOP a chunk.
        logits = jnp.einsum("te,en->tn", normed.reshape(-1, shape[-1]),
                            layer["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        idx, weights = moe.route(logits, c.router())
    with jax.named_scope("moe_experts"):
        routed, sizes = moe.held_experts_ffn(
            flat, idx, weights, layer["experts"], c.held,
            keep.reshape(-1), layer=layer["expert_layer"],
            router=c.router())
    with jax.named_scope("moe_shared"):
        shared = _swiglu(layer["shared"], flat.reshape(shape))
    return x + routed.reshape(shape) + shared, sizes


def _scan_layers(body, x, params: Dict[str, Any], pool: Pool,
                 c: DeepseekConfig):
    """The two layer loops of a forward with the pool in their CARRY
    (``llama_decode._scan_layers``): ``body(x, flat_pool, layer, base,
    moe) -> (x, flat_pool, stats)``. The pool is flat inside, layers and
    pages on one axis, page ``p`` of layer ``l`` at row ``l * (P + 1) +
    p``; the dense layers take the first rows. Returns ``(x, pool,
    stats)``, the expert layers' ``STEP_STATS`` summed."""
    shape = pool["latent"].shape
    flat = pool["latent"].reshape((shape[0] * shape[1],) + shape[2:])
    bases = jnp.arange(shape[0], dtype=jnp.int32) * shape[1]
    nd = c.n_dense_layers
    stats = jnp.zeros((len(STEP_STATS),), jnp.float32)

    def loop(moe_layers):
        def step(carry, inp):
            x, flat, stats = carry
            layer = inp[0]
            if moe_layers:
                layer = {**layer, "experts": params["moe"]["experts"]}
            x, flat, more = body(x, flat, layer, inp[1], moe_layers)
            return (x, flat, stats + more), None
        return step

    carry = (x, flat, stats)
    if nd:
        carry, _ = jax.lax.scan(loop(False), carry,
                                (params["dense"], bases[:nd]))
    if c.n_moe_layers:
        # The experts do not ride the scan's ``xs``: a layer of them
        # sliced out for the grouped matmul's kernel would be a copy
        # (``ops.moe.held_experts_ffn``). The loop hands the stack in
        # whole, with the layer's index.
        rest = {k: v for k, v in params["moe"].items() if k != "experts"}
        rest["expert_layer"] = jnp.arange(c.n_moe_layers, dtype=jnp.int32)
        carry, _ = jax.lax.scan(loop(True), carry, (rest, bases[nd:]))
    x, flat, stats = carry
    return x, {"latent": flat.reshape(shape)}, stats


def _head(params, x, c: DeepseekConfig):
    """(B, E) -> float32 logits (B, V)."""
    x = _normed(x, params["final_norm"], c)
    return jnp.einsum("be,ev->bv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: jax.Array,
                         config: DeepseekConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Pool]:
    """Right-padded ``tokens`` (B, S) from ``pos = prefix_lens`` against
    the pages ``block_tables`` (B, W) maps: the prefix-hit splice, the
    chunked-prefill continuation and (from 0) the whole prefill. Each
    layer scatters its new latent rows into the pool, gathers the row's
    pages, up-projects them to per-head K and V and attends through
    ``latent_prefill_attention``, whose tiles above a row's causal
    frontier are skipped: the pages a power-of-two ``W`` adds beyond
    ``lengths`` cost their up-projection and no score. Returns the logits
    at each row's last real token and the pool."""
    c = config
    B, S = tokens.shape
    T = pool["latent"].shape[2]
    W = block_tables.shape[1]
    C = W * T
    R, nope = c.kv_lora_rank, c.qk_nope_head_dim
    x = _embed(params, tokens)                               # (B, S, E)
    abs_pos = prefix_lens[:, None] + jnp.arange(S)[None, :]  # (B, S)
    cos, sin = rope_at(abs_pos, c.inv_freq(), c.rope_scale)  # (B, S, r/2)
    rows = jnp.arange(B)
    # Pad positions past the page window go to the scratch page, never a
    # clamped real one (``llama_decode.paged_prefill_suffix``).
    pages = jnp.where(
        abs_pos < C,
        block_tables[rows[:, None], jnp.minimum(abs_pos // T, W - 1)], 0)
    offs = abs_pos % T
    keep = jnp.arange(S)[None, :] < (lengths - prefix_lens)[:, None]
    scale = c.softmax_scale

    def attend(q_nope, q_pe, flat, base, layer):
        """Queries (B, S, H, d) over the row's pages: (B, S, H, v)."""
        with jax.named_scope("latent_gather"):
            lat = flat[base + block_tables].reshape(B, C, c.latent_row)
        with jax.named_scope("latent_up"):
            w_uk, w_uv = layer["kv_b"][..., :nope], layer["kv_b"][..., nope:]
            k_nope = jnp.einsum("bkr,rhd->bhkd", lat[..., :R], w_uk)
            v = jnp.einsum("bkr,rhd->bhkd", lat[..., :R], w_uv)
        att = latent_prefill_attention(
            q_nope.transpose(0, 2, 1, 3), q_pe.transpose(0, 2, 1, 3),
            k_nope, lat[..., R:c.latent_dim], v, prefix_lens, scale)
        return att.transpose(0, 2, 1, 3)

    def body(x, flat, layer, base, moe_layer):
        h = _normed(x, layer["attn_norm"], c)
        q_nope, q_pe = _queries(layer, h, c)
        q_pe = rotate_pairs(q_pe, cos[:, :, None], sin[:, :, None],
                            interleaved=True).astype(h.dtype)
        new = _latent(layer, h, c, cos, sin)
        # The gathers follow the scatter, so the suffix sees itself.
        flat = flat.at[base + pages, offs].set(new.astype(flat.dtype))
        att = attend(q_nope, q_pe, flat, base, layer)     # (B, S, H, v)
        att = constrain(att, ("batch", "length", "attn_heads", "head_dim"))
        x = x + jnp.einsum("bshd,hde->bse", att, layer["wo"])
        if not moe_layer:
            return _dense_ffn(layer, x, c), flat, 0.0
        x, sizes = _moe_ffn(layer, x, c, keep)
        return x, flat, moe_step_stats(sizes)

    x, pool, _ = _scan_layers(body, x, params, pool, c)
    idx = jnp.clip(lengths - prefix_lens - 1, 0, S - 1)
    x_last = jnp.take_along_axis(
        x, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return _head(params, x_last, c), pool


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: jax.Array, config: DeepseekConfig,
                  lengths: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Pool]:
    """Whole prefill of right-padded prompts (B, S) into the pages
    ``block_tables`` (B, W) maps, ``W * T >= S``: the suffix program from
    position 0. The keys a prompt attends to are the rows it has just
    stored, rounded to the pool's dtype as every later reader sees
    them."""
    B, S = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    return paged_prefill_suffix(params, tokens, pool, block_tables, config,
                                jnp.zeros((B,), jnp.int32), lengths)


# ------------------------------------------------------------------- decode


def paged_decode_step(params: Dict[str, Any], pool: Pool, view: jax.Array,
                      lengths: jax.Array, tokens: jax.Array,
                      config: DeepseekConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array, jax.Array]:
    """One token per slot in the absorbed form. ``tokens`` (B,) are
    written at ``lengths[b]``; ``view`` is ``live_page_view``'s ``(3, N)``
    list of the pages the stepping slots hold, in groups of ``VIEW_GROUP``
    rows of one slot: the lists of ``paged_decode_attention``, built once
    for every layer. Each layer hands the kernel the flat latent leaf and
    a slot's 128 absorbed queries; the kernel reads the live pages where
    they lie and returns the softmax's sum and the weighted ``c_kv`` rows
    a slot, in float32; ``W_UV`` and ``W_O`` follow once a slot. A slot
    that owns no row (it sees nothing: a sum of 0, selected away) writes
    to the scratch page, is left out of the expert layers' pairs and gets
    finite junk logits. Returns ``(logits, pool, lengths + 1, stats)``,
    ``stats`` the float32 vector ``STEP_STATS`` names."""
    c = config
    B = tokens.shape[0]
    T = pool["latent"].shape[2]
    R, nope = c.kv_lora_rank, c.qk_nope_head_dim
    pages, owner, index = view[0], view[1], view[2]          # (N,) each
    N = pages.shape[0]
    G = VIEW_GROUP
    if N % G:
        raise ValueError(f"a view of {N} rows is not whole groups of {G}")
    pos = lengths
    cos, sin = rope_at(pos[:, None], c.inv_freq(), c.rope_scale)
    x = _embed(params, tokens)[:, None]                      # (B, 1, E)
    member = owner[None, :] == jnp.arange(B)[:, None]        # (B, N)
    steps = member.any(axis=1)                               # (B,)
    # The page a slot writes: its row at index pos // T, else scratch.
    page = jnp.sum(jnp.where(
        member & (index[None, :] == (pos // T)[:, None]),
        pages[None, :], 0), axis=1)
    off = pos % T
    # A group's owner is its first row's (``live_page_view``).
    lists = page_lists(pages.reshape(N // G, G),
                       owner.reshape(N // G, G)[:, 0],
                       index.reshape(N // G, G), pos, T)

    def body(x, flat, layer, base, moe_layer):
        h = _normed(x, layer["attn_norm"], c)
        q_nope, q_pe = _queries(layer, h, c)              # (B, 1, H, d)
        q_pe = rotate_pairs(q_pe, cos[:, :, None], sin[:, :, None],
                            interleaved=True).astype(h.dtype)
        new = _latent(layer, h, c, cos, sin)[:, 0]        # (B, 640)
        flat = flat.at[base + page, off].set(new.astype(flat.dtype))
        with jax.named_scope("latent_attn"):
            w_uk, w_uv = layer["kv_b"][..., :nope], layer["kv_b"][..., nope:]
            q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
            q_lat = jnp.concatenate([q_abs, q_pe[:, 0], jnp.zeros(
                q_abs.shape[:2] + (c.latent_row - c.latent_dim,),
                q_abs.dtype)], -1)
            _, total, acc = paged_decode_attention(
                q_lat.astype(flat.dtype), flat, None, lists,
                c.softmax_scale, base, value_width=R)
            total = total[..., None]
            o_lat = jnp.where(total > 0.0, acc / total, 0.0).astype(h.dtype)
            att = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)
        att = constrain(att, ("batch", "attn_heads", "head_dim"))
        x = x + jnp.einsum("bhd,hde->be", att, layer["wo"])[:, None]
        if not moe_layer:
            return _dense_ffn(layer, x, c), flat, 0.0
        x, sizes = _moe_ffn(layer, x, c, steps[:, None])
        return x, flat, moe_step_stats(sizes)

    x, pool, stats = _scan_layers(body, x, params, pool, c)
    return _head(params, x[:, 0], c), pool, pos + 1, stats
