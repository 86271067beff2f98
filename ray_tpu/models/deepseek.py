"""DeepSeek-V2 (DeepSeek-AI, "DeepSeek-V2: A Strong, Economical, and
Efficient Mixture-of-Experts Language Model", 2024; the published
``modeling_deepseek.py``): multi-head latent attention (MLA) in every
layer, a dense SwiGLU in the leading layers and, in the others, routed
experts chosen by group-limited top-k beside shared experts.

This module is the model's data: its configuration, its weights and the
pieces both served forms of its attention share. The programs the decode
engine runs are in ``deepseek_decode.py``; the model is served only (at
16 B a parameter no cut of it trains on one chip).

A chip may hold its share of a layer and not the whole of it
(``experts_held``: which of the ``n_routed_experts`` live here; the
vocabulary's rows likewise, as a smaller ``vocab_size``): the router keeps
its published width, and ``ops.moe.held_experts_ffn`` computes what the
held experts give.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import rotary
from ray_tpu.ops.moe import Router


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 102400
    dim: int = 5120
    n_layers: int = 60
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 12288            # the dense layers' SwiGLU
    moe_mlp_dim: int = 1536         # one expert's
    n_dense_layers: int = 1         # ``first_k_dense_replace``
    n_routed_experts: int = 160     # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    n_shared_experts: int = 2
    top_k: int = 6
    n_group: int = 8
    topk_group: int = 3
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 16.0
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN (``rope_scaling``); factor 1 is plain RoPE.
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_seq_len: int = 163840
    dtype: Any = jnp.bfloat16

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def latent_dim(self) -> int:
        """What is cached a token a layer: ``c_kv`` and the shared
        rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The width of a row of the pool: ``latent_dim`` rounded up to
        the device's 128-wide tile (576 -> 640), the rest zeros. The tiled
        layout pads a 576-wide row to 640 in memory whatever is declared,
        and a program that scatters into an array it has to re-tile
        copies it whole (1.7 GB a call at the served size, by the
        compiler's memory analysis); declared at the tile's width the
        pool is written in place."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = rotary.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def rope_scale(self) -> float:
        """What the published code multiplies cos and sin by."""
        return (rotary.yarn_mscale(self.rope_factor, self.rope_mscale)
                / rotary.yarn_mscale(self.rope_factor,
                                     self.rope_mscale_all_dim))

    def inv_freq(self) -> jax.Array:
        return rotary.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_len, self.rope_beta_fast,
            self.rope_beta_slow)

    def router(self) -> Router:
        return Router(experts=self.n_routed_experts, top_k=self.top_k,
                      groups=self.n_group,
                      top_groups=self.topk_group,
                      renormalise=self.norm_topk_prob,
                      scale=self.routed_scaling_factor)


PRESETS = {
    # Toy widths for the CPU tests: 1 dense + 2 MoE layers, half of 16
    # experts in 4 groups held, so tokens route to absent experts too.
    "debug": DeepseekConfig(
        vocab_size=128, dim=64, n_layers=3, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, mlp_dim=128, moe_mlp_dim=32, n_dense_layers=1,
        n_routed_experts=16, experts_held=(0, 8), n_shared_experts=2,
        top_k=3, n_group=4, topk_group=2, rope_original_max_len=64,
        max_seq_len=1024, dtype=jnp.float32),
}

NORM_LEAVES = ("attn_norm", "q_norm", "kv_norm", "mlp_norm", "final_norm")


def _shapes(c: DeepseekConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, fan_in)``; fan_in ``None`` is a norm scale
    (ones). Layers are stacked on a leading axis, the dense ones and the
    expert ones apart."""
    e, h = c.dim, c.n_heads

    def attn(n):
        return {
            "attn_norm": ((n, e), None),
            "q_a": ((n, e, c.q_lora_rank), e),
            "q_norm": ((n, c.q_lora_rank), None),
            "q_b": ((n, c.q_lora_rank, h, c.qk_head_dim), c.q_lora_rank),
            "kv_a": ((n, e, c.latent_dim), e),
            "kv_norm": ((n, c.kv_lora_rank), None),
            # W_UK | W_UV side by side on the last axis.
            "kv_b": ((n, c.kv_lora_rank, h,
                      c.qk_nope_head_dim + c.v_head_dim), c.kv_lora_rank),
            "wo": ((n, h, c.v_head_dim, e), h * c.v_head_dim),
            "mlp_norm": ((n, e), None),
        }

    def swiglu(lead, width):
        return {"w_gate": (lead + (e, width), e),
                "w_up": (lead + (e, width), e),
                "w_down": (lead + (width, e), width)}

    nd, nm = c.n_dense_layers, c.n_moe_layers
    return {
        "tok_embed": ((c.vocab_size, e), 1.0),
        "dense": {**attn(nd), **swiglu((nd,), c.mlp_dim)},
        "moe": {**attn(nm),
                "router": ((nm, e, c.n_routed_experts), e),
                "experts": swiglu((nm, c.held[1]), c.moe_mlp_dim),
                "shared": swiglu(
                    (nm,), c.n_shared_experts * c.moe_mlp_dim)},
        "final_norm": ((e,), None),
        "lm_head": ((e, c.vocab_size), e),
    }


def init_params(config: DeepseekConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made LEAF BY LEAF in ``config.dtype``: at the
    served cut a float32 tree is 20 GB and never exists. A stacked leaf is
    filled one layer at a time, so the float32 transient is one layer of
    one leaf."""
    dtype = jnp.dtype(config.dtype)

    def leaf(path, spec):
        shape, fan_in = spec
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        if 0 in shape:      # no layer of this kind
            return jnp.zeros(shape, dtype)
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))
        scale = float(fan_in) ** -0.5

        def one(k, shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(dtype)

        if len(shape) < 3:
            return jax.jit(one, static_argnums=1)(k, shape)

        def fill(k):
            return jax.lax.fori_loop(
                0, shape[0],
                lambda i, buf: buf.at[i].set(
                    one(jax.random.fold_in(k, i), shape[1:])),
                jnp.zeros(shape, dtype))

        return jax.jit(fill)(k)

    def walk(tree, prefix):
        return {name: (walk(sub, prefix + name + "/")
                       if isinstance(sub, dict)
                       else leaf(prefix + name, sub))
                for name, sub in tree.items()}

    return walk(_shapes(config), "")


def param_count(config: DeepseekConfig) -> int:
    import math

    return sum(math.prod(spec[0]) for spec in jax.tree.leaves(
        _shapes(config), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[0], tuple)))
