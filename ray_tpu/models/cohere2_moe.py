"""Command A+ (Cohere, ``model_type`` ``cohere2_moe``; the published
``config.json`` of ``command-a-plus-05-2026``): a decoder of PARALLEL
blocks. Layer ``l`` takes ONE LayerNorm of the stream (the mean subtracted,
a scale, no bias), and both halves of the block read it: attention of the
kind ``layer_types[l]`` names, and the feed-forward; one residual add takes
both, ``x <- x + attn(LN(x)) + ffn(LN(x))``.

* ``sliding_attention`` (``window``): the last ``sliding_window`` = 4,096
  tokens, rotary on the whole 128 of each head, pairs ``(2i, 2i + 1)``
  (``rope_gptj``), base 50,000;
* ``full_attention`` (``full``): causal over everything and NO position
  term at all.

Both have 128 query heads of 128 over 8 key-value heads, keys as wide as
values, no biases, no q/k norm, no sink. The feed-forward of every layer
(``first_k_dense_replace`` 0) is 128 routed experts, sigmoid scores, the 8
largest chosen and renormalised, beside 4 shared experts whose outputs are
AVERAGED (``shared_expert_combination_strategy``); every expert a SwiGLU of
4,096. The head is the embedding, transposed, times ``logit_scale``.

This module is the model's data: its configuration and its weights. The
programs the decode engine runs are in ``cohere2_moe_decode.py``; the model
is served only, and the vision tower of the release is not here.

A chip may hold its share of a layer and not the whole of it
(``experts_held``; the vocabulary's rows as a smaller ``vocab_size``), as in
``deepseek.py`` and ``mimo.py``. Consecutive layers of one kind form a
SEGMENT, whose leaves are stacked on a leading axis: the programs run one
``scan`` a segment (a period ``[window, window, window, full]`` is 2
segments, 32 layers 16)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.ops.moe import Router

FULL, WINDOW = "full", "window"
# ``layer_types`` as published, 32 long.
LAYER_TYPES = (("sliding_attention",) * 3 + ("full_attention",)) * 8
_KIND_OF = {"sliding_attention": WINDOW, "full_attention": FULL}


class Segment(NamedTuple):
    kind: str        # FULL or WINDOW
    layers: int
    first: int       # index of its first layer among the layers of its kind


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    head_dim: int = 128
    n_kv_heads: int = 8
    rope_theta: float = 5e4
    window: int = 4096
    # Read up to ``n_layers``.
    layer_types: Tuple[str, ...] = LAYER_TYPES
    mlp_dim: int = 4096             # one expert's, routed or shared
    n_routed_experts: int = 128     # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    top_k: int = 8
    norm_topk_prob: bool = True
    n_shared_experts: int = 4       # averaged
    logit_scale: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 200000
    dtype: Any = jnp.bfloat16

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def kind(self, layer: int) -> str:
        return _KIND_OF[self.layer_types[layer]]

    def kind_layers(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in range(self.n_layers))

    @property
    def kv_width(self) -> int:
        """A token's keys (or values) of one layer, heads flat."""
        return self.n_kv_heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def inv_freq(self) -> jax.Array:
        d = self.head_dim
        return 1.0 / (self.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d))

    def segments(self) -> List[Segment]:
        if len(self.layer_types) < self.n_layers:
            raise ValueError(f"layer_types is shorter than "
                             f"{self.n_layers} layers")
        out: List[Segment] = []
        seen = {FULL: 0, WINDOW: 0}
        for l in range(self.n_layers):
            kind = self.kind(l)
            if out and out[-1].kind == kind:
                out[-1] = out[-1]._replace(layers=out[-1].layers + 1)
            else:
                out.append(Segment(kind, 1, seen[kind]))
            seen[kind] += 1
        return out

    def router(self) -> Router:
        return Router(experts=self.n_routed_experts, top_k=self.top_k,
                      renormalise=self.norm_topk_prob, score="sigmoid")


PRESETS = {
    # Toy widths for the CPU tests, one period of the layer list: half of
    # 16 experts held, so tokens route to absent experts too; a window of
    # 12 tokens, which pages of 4 and 8 cross; two shared experts, so that
    # their average is not their sum; a head scale that is not 1.
    "debug": Cohere2MoeConfig(
        vocab_size=128, dim=64, n_layers=4, n_heads=8, head_dim=16,
        n_kv_heads=2, window=12, mlp_dim=32, n_routed_experts=16,
        experts_held=(0, 8), top_k=3, n_shared_experts=2, logit_scale=0.5,
        max_seq_len=1024, dtype=jnp.float32),
}

NORM_LEAVES = ("norm", "final_norm")
# Leaves that stay float32 whatever the compute dtype is.
FLOAT32_LEAVES = NORM_LEAVES


def _shapes(c: Cohere2MoeConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, fan_in)``: a number draws ``N(0, 1 /
    fan_in)`` in the compute dtype; ``None`` is a norm scale (ones). A
    segment's layers are stacked on a leading axis."""
    e, h, d, m = c.dim, c.n_heads, c.head_dim, c.mlp_dim
    shared = c.n_shared_experts * m

    def swiglu(lead, width, fan_down):
        return {"w_gate": (lead + (e, width), e),
                "w_up": (lead + (e, width), e),
                "w_down": (lead + (width, e), fan_down)}

    def segment(seg: Segment):
        n = seg.layers
        return {
            "norm": ((n, e), None),
            # Queries | keys | values side by side (a layout, no
            # mathematics).
            "wqkv": ((n, e, h * d + 2 * c.kv_width), e),
            "wo": ((n, h, d, e), h * d),
            "router": ((n, e, c.n_routed_experts), e),
            "experts": swiglu((n, c.held[1]), m, m),
            # The shared experts side by side: ONE SwiGLU of their widths
            # together is their SUM; the average's 1 / n is the program's.
            "shared": swiglu((n,), shared, m),
        }

    return {
        # Rows of N(0, 4 / dim), as ``phi4flash.py``'s: under N(0, 1) a
        # position's own token outweighs every layer in a tied head.
        "tok_embed": ((c.vocab_size, e), e / 4.0),
        "segments": [segment(s) for s in c.segments()],
        "final_norm": ((e,), None),
    }


def init_params(config: Cohere2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made leaf by leaf in ``config.dtype``
    (``moe_decode.init_leaves``): a float32 tree of the served cut would be
    19 GB and never exists."""
    return moe_decode.init_leaves(_shapes(config), key, config.dtype)


def param_count(config: Cohere2MoeConfig) -> int:
    return moe_decode.count_leaves(_shapes(config))
