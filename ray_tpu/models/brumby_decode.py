"""The programs ``serve/decode.py`` runs for Brumby (``brumby.py``): what
the engine asks of a model module (docs/SERVING.md, "The model seam"), for
a model with NO KIND OF PAGE. ``page_kinds`` is empty: every layer's mixer
is a power-retention layer, whose whole cache is a STATE a slot
(``slot_state``), so the pool is two leaves and nothing is paged:

* ``S`` ``[layers, slots + 1, n_kv_heads, head_dim, R]`` float32: a key-value
  head's ``sum_i decay v_i phi(k_i)^T``, a value's numbers on sublanes and
  the ``R`` rows of the expansion on lanes (``ops/power_retention.py``; at
  the served widths 9,216 rows for the 8,256 distinct monomials);
* ``z`` ``[layers, slots + 1, n_kv_heads, R]`` float32: ``sum_i decay
  phi(k_i)``, the normaliser.

Row ``slots`` is scratch, where pad rows write. 38.0 MB a slot a layer at
the served widths, whatever the context: the keys and values of 9,288
tokens of the same heads.

* **prefill** (``paged_prefill``, ``paged_prefill_suffix``): a chunk runs
  every layer at every position from the state the last chunk left (zero
  where the row starts at position 0) and leaves its own
  (``retention_chunk``). The final norm and the head run at a row's LAST
  position and only in a program some row of which ends its prompt
  (``block_tables["ends"]``).
* **decode** (``paged_decode_step``): one token a slot through every layer;
  ``view`` is the engine's mask of the slots that step (there is no page to
  view). A layer's state is read and written once, where it lies
  (``retention_step``); a slot outside the step keeps its state bit for
  bit.

The engine's optional program (``shard_decode_state``) is not here: the
engine refuses a mesh.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.models.brumby import FLOAT32_LEAVES, BrumbyConfig
# Shared with every model the engine runs, and part of what this module
# provides: the prefill buckets and the fused sampler.
from ray_tpu.models.llama_decode import (cache_bucket,  # noqa: F401
                                         sample_batch)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.power_retention import retention_chunk, retention_step
from ray_tpu.ops.rotary import rope_at, rotate_pairs
from ray_tpu.parallel.sharding import constrain

Pool = Dict[str, jax.Array]

# The most tokens (rows x bucket) the engine gives one prefill program: a
# row's gathered state is 38.0 MB a layer and a sub-chunk's expanded
# queries 0.74 MB a token, beside a pool that fills the chip.
PREFILL_TOKENS_MAX = 2048


def page_kinds(config: BrumbyConfig) -> Dict[str, Dict[str, Any]]:
    """No kind of page: nothing in this model is indexed by position."""
    return {}


def slot_state(config: BrumbyConfig) -> Tuple[str, ...]:
    """The pool's leaves, all of them indexed by SLOT."""
    return ("S", "z")


def compute_weights(params: Dict[str, Any], config: BrumbyConfig,
                    donate: bool = False) -> Dict[str, Any]:
    return moe_decode.cast_weights(params, config.dtype, FLOAT32_LEAVES,
                                   donate)


def init_page_pool(config: BrumbyConfig, pages: Dict[str, int],
                   page_tokens: int, dtype=None, slots: int = 0) -> Pool:
    """Zeroed state: ``slots`` rows a layer and the scratch row behind
    them. ``pages`` names no kind and ``page_tokens`` sizes nothing."""
    c = config
    del pages, page_tokens, dtype
    head = (c.n_layers, slots + 1, c.n_kv_heads)
    return {"S": jnp.zeros(head + (c.head_dim, c.state_rows), jnp.float32),
            "z": jnp.zeros(head + (c.state_rows,), jnp.float32)}


# ------------------------------------------------------------ layer pieces
#
# Every piece takes the residual stream (B, T, E), T = 1 in a decode step;
# it is float32 through the layers and the matmuls read a norm's copy in the
# compute dtype.


def _project(layer, x, c: BrumbyConfig, cos, sin):
    """``q`` (B, T, J, G, d) and ``k``, ``v`` (B, T, J, d) in the compute
    dtype, head-normed and rotated at ``cos`` / ``sin`` (B, T, d / 2), and
    the gate's logarithm (B, T, J) float32."""
    lead = x.shape[:-1]
    u = rms_norm(x, layer["norm1"], c.norm_eps).astype(c.dtype)
    q = jnp.einsum("...e,ef->...f", u, layer["wq"]).reshape(
        lead + (c.n_kv_heads, c.group, c.head_dim))
    k = jnp.einsum("...e,ef->...f", u, layer["wk"]).reshape(
        lead + (c.n_kv_heads, c.head_dim))
    v = jnp.einsum("...e,ef->...f", u, layer["wv"]).reshape(k.shape)
    q = rotate_pairs(rms_norm(q, layer["q_norm"], c.norm_eps),
                     cos[..., None, None, :], sin[..., None, None, :])
    k = rotate_pairs(rms_norm(k, layer["k_norm"], c.norm_eps),
                     cos[..., None, :], sin[..., None, :])
    log_g = jax.nn.log_sigmoid(
        jnp.einsum("...e,ej->...j", u, layer["wg"],
                   preferred_element_type=jnp.float32))
    return q.astype(c.dtype), k.astype(c.dtype), v, log_g


def _mix_out(layer, o, x, c: BrumbyConfig):
    """``x + W_o o`` for the heads' outputs ``o`` (B, T, J, G, d)."""
    o = o.reshape(o.shape[:2] + (c.n_heads, c.head_dim)).astype(c.dtype)
    # The pre-contraction anchors of ``llama_decode`` (no-ops without a
    # mesh, which this model has no rules for): no contraction is split.
    o = constrain(o, ("batch", "length", "attn_heads", "head_dim"))
    return x + jnp.einsum("bthd,hde->bte", o, layer["wo"])


def _mlp(layer, x, c: BrumbyConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["norm2"], c.norm_eps).astype(c.dtype)
        gate = jnp.einsum("bte,ef->btf", h, layer["w_gate"])
        up = jnp.einsum("bte,ef->btf", h, layer["w_up"])
        ffn = constrain(jax.nn.silu(gate) * up,
                        ("batch", "length", "mlp_hidden"))
        return x + jnp.einsum("btf,fe->bte", ffn, layer["w_down"])


def _head(params, x, c: BrumbyConfig):
    """(B, E) -> float32 logits (B, V)."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], c.norm_eps).astype(c.dtype)
        return jnp.einsum("be,ev->bv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _layers(c: BrumbyConfig, params):
    return params["layers"], jnp.arange(c.n_layers, dtype=jnp.int32)


# ------------------------------------------------------------------ prefill


def paged_prefill_suffix(params: Dict[str, Any], tokens: jax.Array,
                         pool: Pool, block_tables: Dict[str, jax.Array],
                         config: BrumbyConfig, prefix_lens: jax.Array,
                         lengths: jax.Array) -> Tuple[jax.Array, Pool]:
    """Right-padded ``tokens`` (B, T) from ``pos = prefix_lens``: the
    chunked-prefill continuation and (from 0) the whole prefill.
    ``block_tables`` maps ``"slots"`` (B,) the row's slot (a pad row names
    the scratch row) and ``"ends"`` (B,) whether the row's prompt ends
    here; there is no table of pages. A row at ``prefix_lens`` 0 starts
    from a zero state. Returns the logits at the last real token of the
    rows that end (zeros where no row does) and the pool."""
    c = config
    B, T = tokens.shape
    slots, ends = block_tables["slots"], block_tables["ends"]
    x = params["tok_embed"][tokens].astype(jnp.float32)      # (B, T, E)
    cos, sin = rope_at(prefix_lens[:, None] + jnp.arange(T)[None, :],
                       c.inv_freq)
    n_real = lengths - prefix_lens
    fresh = prefix_lens == 0

    def layer_fn(carry, inp):
        x, S, z = carry
        layer, at = inp
        with jax.named_scope("retention_proj"):
            q, k, v, log_g = _project(layer, x, c, cos, sin)
        with jax.named_scope("retention_chunk"):
            S0 = jnp.where(fresh[:, None, None, None], 0.0, S[at, slots])
            z0 = jnp.where(fresh[:, None, None], 0.0, z[at, slots])
            o, S1, z1 = retention_chunk(
                q, k, v, log_g, S0, z0, scale=c.scale, block=c.phi_block,
                eps=c.retention_eps, lengths=n_real)
            S = S.at[at, slots].set(S1)
            z = z.at[at, slots].set(z1)
        with jax.named_scope("retention_proj"):
            x = _mix_out(layer, o, x, c)
        return (_mlp(layer, x, c), S, z), None

    (x, S, z), _ = jax.lax.scan(layer_fn, (x, pool["S"], pool["z"]),
                                _layers(c, params))
    idx = jnp.clip(n_real - 1, 0, T - 1)[:, None, None].astype(jnp.int32)
    logits = jax.lax.cond(
        jnp.any(ends),
        lambda: _head(params, jnp.take_along_axis(x, idx, axis=1)[:, 0], c),
        lambda: jnp.zeros((B, params["lm_head"].shape[1]), jnp.float32))
    return logits, {**pool, "S": S, "z": z}


def paged_prefill(params: Dict[str, Any], tokens: jax.Array, pool: Pool,
                  block_tables: Dict[str, jax.Array], config: BrumbyConfig,
                  lengths: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Pool]:
    """Whole prefill of right-padded prompts (B, T): the suffix program
    from position 0."""
    B, T = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    return paged_prefill_suffix(params, tokens, pool, block_tables, config,
                                jnp.zeros((B,), jnp.int32), lengths)


# ------------------------------------------------------------------- decode


def paged_decode_step(params: Dict[str, Any], pool: Pool, view: jax.Array,
                      lengths: jax.Array, tokens: jax.Array,
                      config: BrumbyConfig
                      ) -> Tuple[jax.Array, Pool, jax.Array]:
    """One token per slot at position ``lengths[b]``. ``view`` (B,) bool
    names the slots that step; another keeps its state bit for bit and its
    logits are finite junk. Returns ``(logits, pool, lengths + 1)``."""
    c = config
    steps = view
    x = params["tok_embed"][tokens].astype(jnp.float32)[:, None]  # B 1 E
    cos, sin = rope_at(lengths[:, None], c.inv_freq)

    def layer_fn(carry, inp):
        x, S, z = carry
        layer, at = inp
        with jax.named_scope("retention_proj"):
            q, k, v, log_g = _project(layer, x, c, cos, sin)
        o, S, z = retention_step(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], S,
                                 z, steps, at, scale=c.scale,
                                 block=c.phi_block, eps=c.retention_eps)
        with jax.named_scope("retention_proj"):
            x = _mix_out(layer, o[:, None], x, c)
        return (_mlp(layer, x, c), S, z), None

    (x, S, z), _ = jax.lax.scan(layer_fn, (x, pool["S"], pool["z"]),
                                _layers(c, params))
    return _head(params, x[:, 0], c), {**pool, "S": S, "z": z}, lengths + 1
