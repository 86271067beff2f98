"""MiMo-V2 (Xiaomi, ``model_type`` ``mimo_v2``; the published
``config.json`` of MiMo-V2.5): a decoder whose layers attend in one of two
KINDS, named layer by layer by ``hybrid_layer_pattern``: ``full`` (0: causal
over everything, 4 key-value heads) and ``window`` (1: the last
``sliding_window`` = 128 tokens, 8 key-value heads, and one learned SINK
logit a head in the softmax's denominator). Queries and keys are 192 wide,
values 128; rotary turns the first ``int(192 x 0.334)`` = 64 of each 192,
with a base of its own a kind; values are scaled by
``attention_value_scale``. ``moe_layer_freq`` says layer by layer whether the
feed-forward is a dense SwiGLU (0) or routed experts (1): sigmoid scores, a
selection bias, top 8 of 256 without groups, renormalised, no shared expert.

This module is the model's data: its configuration and its weights. The
programs the decode engine runs are in ``mimo_decode.py``; the model is
served only. The vision and audio towers and the multi-token-prediction
layers of the release are not here (the language model is).

A chip may hold its share of a layer and not the whole of it
(``experts_held``; the vocabulary's rows as a smaller ``vocab_size``), as in
``deepseek.py``.

Consecutive layers of one kind and one feed-forward form a SEGMENT, whose
leaves are stacked on a leading axis: the programs run one ``scan`` a
segment (7 layers ``[0,1,1,1,1,0,1]`` are 4 segments, 48 layers 17)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.ops.moe import Router

FULL, WINDOW = "full", "window"
# ``hybrid_layer_pattern`` and ``moe_layer_freq`` as published, 48 long.
HYBRID_LAYER_PATTERN = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)
MOE_LAYER_FREQ = (0,) + (1,) * 47


class Segment(NamedTuple):
    kind: str        # FULL or WINDOW
    moe: bool
    layers: int
    first: int       # index of its first layer among the layers of its kind


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    dim: int = 4096
    n_layers: int = 48
    n_heads: int = 64
    head_dim: int = 192             # queries and keys
    v_head_dim: int = 128
    n_kv_heads: int = 4             # the full layers'
    swa_n_kv_heads: int = 8         # the window layers'
    rotary_dim: int = 64            # int(head_dim * partial_rotary_factor)
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    window: int = 128
    value_scale: float = 0.707
    swa_sink: bool = True           # ``add_swa_attention_sink_bias``
    full_sink: bool = False         # ``add_full_attention_sink_bias``
    # Read up to ``n_layers``: 0 = full / dense, 1 = window / experts.
    layer_pattern: Tuple[int, ...] = HYBRID_LAYER_PATTERN
    moe_pattern: Tuple[int, ...] = MOE_LAYER_FREQ
    mlp_dim: int = 16384            # the dense layers' SwiGLU
    moe_mlp_dim: int = 2048         # one expert's
    n_routed_experts: int = 256     # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    def kind(self, layer: int) -> str:
        return WINDOW if self.layer_pattern[layer] else FULL

    def kind_layers(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in range(self.n_layers))

    def kv_heads(self, kind: str) -> int:
        return self.swa_n_kv_heads if kind == WINDOW else self.n_kv_heads

    def theta(self, kind: str) -> float:
        return self.swa_rope_theta if kind == WINDOW else self.rope_theta

    def has_sink(self, kind: str) -> bool:
        return self.swa_sink if kind == WINDOW else self.full_sink

    @property
    def n_moe_layers(self) -> int:
        return sum(self.moe_pattern[:self.n_layers])

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def inv_freq(self, kind: str) -> jax.Array:
        r = self.rotary_dim
        return 1.0 / (self.theta(kind) ** (
            jnp.arange(0, r, 2, dtype=jnp.float32) / r))

    def segments(self) -> List[Segment]:
        if len(self.layer_pattern) < self.n_layers or \
                len(self.moe_pattern) < self.n_layers:
            raise ValueError(f"the layer lists are shorter than "
                             f"{self.n_layers} layers")
        out: List[Segment] = []
        seen = {FULL: 0, WINDOW: 0}
        for l in range(self.n_layers):
            kind, moe = self.kind(l), bool(self.moe_pattern[l])
            if out and (out[-1].kind, out[-1].moe) == (kind, moe):
                out[-1] = out[-1]._replace(layers=out[-1].layers + 1)
            else:
                out.append(Segment(kind, moe, 1, seen[kind]))
            seen[kind] += 1
        return out

    def router(self) -> Router:
        return Router(experts=self.n_routed_experts, top_k=self.top_k,
                      renormalise=self.norm_topk_prob,
                      scale=self.routed_scaling_factor, score="sigmoid")


PRESETS = {
    # Toy widths for the CPU tests, the served cut's seven layers: half of
    # 16 experts held, so tokens route to absent experts too; a window of
    # 12 tokens, which pages of 4 and 8 cross.
    "debug": MimoConfig(
        vocab_size=128, dim=64, n_layers=7, n_heads=8, head_dim=24,
        v_head_dim=16, n_kv_heads=2, swa_n_kv_heads=4, rotary_dim=8,
        window=12, mlp_dim=128, moe_mlp_dim=32, n_routed_experts=16,
        experts_held=(0, 8), top_k=3, max_seq_len=1024, dtype=jnp.float32),
}

NORM_LEAVES = ("attn_norm", "mlp_norm", "final_norm")
# Leaves that stay float32 whatever the compute dtype is: the norms'
# scales, the sink logits and the router's selection bias.
FLOAT32_LEAVES = NORM_LEAVES + ("sink", "router_bias")


def _shapes(c: MimoConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, fan_in)``: a number draws ``N(0, 1 /
    fan_in)`` in the compute dtype; ``None`` is a norm scale (ones);
    ``"sink"`` and ``"bias"`` draw float32 ``N(0, 1)`` and ``N(0, 0.01)``
    (zeros would test nothing: a trained sink and a trained selection bias
    are not zero). A segment's layers are stacked on a leading axis."""
    e, h = c.dim, c.n_heads

    def swiglu(lead, width):
        return {"w_gate": (lead + (e, width), e),
                "w_up": (lead + (e, width), e),
                "w_down": (lead + (width, e), width)}

    def segment(seg: Segment):
        n, kv = seg.layers, c.kv_heads(seg.kind)
        out = {
            "attn_norm": ((n, e), None),
            # ``fused_qkv``: queries | keys | values side by side.
            "wqkv": ((n, e, h * c.head_dim + kv * c.head_dim
                      + kv * c.v_head_dim), e),
            "wo": ((n, h, c.v_head_dim, e), h * c.v_head_dim),
            "mlp_norm": ((n, e), None),
        }
        if c.has_sink(seg.kind):
            out["sink"] = ((n, h), "sink")
        if seg.moe:
            out["router"] = ((n, e, c.n_routed_experts), e)
            out["router_bias"] = ((n, c.n_routed_experts), "bias")
            out["experts"] = swiglu((n, c.held[1]), c.moe_mlp_dim)
        else:
            out.update(swiglu((n,), c.mlp_dim))
        return out

    return {
        "tok_embed": ((c.vocab_size, e), 1.0),
        "segments": [segment(s) for s in c.segments()],
        "final_norm": ((e,), None),
        "lm_head": ((e, c.vocab_size), e),
    }


def init_params(config: MimoConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made leaf by leaf in ``config.dtype``
    (``moe_decode.init_leaves``): a float32 tree of the served cut would be
    14 GB and never exists. The sink logits draw float32 ``N(0, 1)``, the
    selection bias ``N(0, 0.01)``."""
    return moe_decode.init_leaves(_shapes(config), key, config.dtype,
                                  {"sink": 1.0, "bias": 0.1})


def param_count(config: MimoConfig) -> int:
    return moe_decode.count_leaves(_shapes(config))
