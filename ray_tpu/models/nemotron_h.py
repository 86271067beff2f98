"""Nemotron-H (NVIDIA, ``model_type`` ``nemotron_h``; the published
``config.json`` of ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, the
Nemotron-H report arXiv:2504.03624): a decoder whose every layer is ONE
part, ``x <- x + part(RMSNorm(x))``, RMSNorm with a weight, one residual
add, the stream in the model's dtype. ``hybrid_override_pattern`` names the
part of each layer, one letter a layer:

* ``M``, Mamba-2 (arXiv:2405.21060): ``[z | xBC | dt] = u W_in``; ``xBC <-
  silu(conv(xBC))``, a depthwise causal convolution over the last
  ``d_conv`` positions with a bias; ``xBC`` splits into ``x`` (heads x
  head_dim) and ``B``, ``C`` (groups x state); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, ONE decay a head; a head keeps a state
  ``S`` (head_dim x state, float32), ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops/ssd.py``); then the gated group
  norm, ``y <- y * silu(z)`` RMS-normalised inside each group's channels
  times a weight; ``out = y W_out``. It keeps a STATE a sequence: ``S`` and
  the last ``d_conv - 1`` inputs of its convolution.
* ``*``, attention: ``n_heads`` query heads over ``n_kv_heads`` key-value
  heads, causal over everything, no bias and NO positional term (the Mamba
  layers carry order); its keys and values are the one kind of page.
* ``E``, experts in a LATENT space: ``scores = sigmoid(u W_r)`` in float32,
  the ``top_k`` largest ``scores + bias`` chosen, their weights the scores
  themselves renormalised, times ``routed_scale``; ``l = u W_1`` (dim ->
  latent); expert ``e`` is NOT gated, ``f_e(l) = relu(l U_e)^2 D_e`` in the
  latent width; ``routed = (sum_e w_e f_e(l)) W_2``; one shared expert on
  the full width, ``relu(u U_s)^2 D_s``; ``part = routed + shared``.

After the last layer ``norm_f`` and an untied head. The multi-token
prediction layer of the release is not here (the engine has no
speculation).

This module is the model's data: its configuration and its weights. The
programs the decode engine runs are in ``nemotron_h_decode.py``; the model
is served only. A chip may hold its share of a layer and not the whole of
it (``experts_held``; the vocabulary's rows as a smaller ``vocab_size``), as
in ``deepseek.py``. Consecutive layers of one letter form a SEGMENT, whose
leaves are stacked on a leading axis: the programs run one ``scan`` a
segment (the published pattern alternates, so its runs are one layer
long)."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.ops.moe import Router

MAMBA, EXPERTS, FULL = "mamba", "experts", "full"
_KIND_OF = {"M": MAMBA, "E": EXPERTS, "*": FULL}
# What a segment's scan carries of the pool (``moe_decode.scan_segments``).
_CARRIES = {MAMBA: ("ssm", "conv"), EXPERTS: (), FULL: ("full_k", "full_v")}
# ``hybrid_override_pattern`` as published, 88 long: 40 M, 40 E, 8 *.
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


class Segment(NamedTuple):
    kind: str        # MAMBA, EXPERTS or FULL
    layers: int
    first: int       # index of its first layer among the layers of its kind
    moe: bool        # whether its layers hold routed experts
    carries: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    dim: int = 4096
    n_layers: int = 88
    # Read up to ``n_layers``.
    pattern: str = PATTERN
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    d_conv: int = 4
    chunk_size: int = 128
    latent: int = 1024              # the routed experts' width in and out
    expert_dim: int = 2688
    shared_dim: int = 5376
    n_routed_experts: int = 512     # the router's width
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    top_k: int = 22
    norm_topk_prob: bool = True
    routed_scale: float = 5.0
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        letters = self.pattern[:self.n_layers]
        if len(letters) < self.n_layers or set(letters) - set(_KIND_OF):
            raise ValueError(
                f"nemotron_h: the pattern {self.pattern!r} does not name "
                f"{self.n_layers} layers by M, E and *")
        if self.n_heads % self.n_kv_heads \
                or self.mamba_heads % self.ssm_groups:
            raise ValueError("nemotron_h: heads do not divide into groups")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """``xBC``: the channels the convolution runs over."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def kv_width(self) -> int:
        """A token's keys (or values) of one attention layer, heads flat."""
        return self.n_kv_heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def kind(self, layer: int) -> str:
        return _KIND_OF[self.pattern[layer]]

    def kind_layers(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in range(self.n_layers))

    def segments(self) -> List[Segment]:
        out: List[Segment] = []
        seen = {k: 0 for k in _CARRIES}
        for l in range(self.n_layers):
            kind = self.kind(l)
            if out and out[-1].kind == kind:
                out[-1] = out[-1]._replace(layers=out[-1].layers + 1)
            else:
                out.append(Segment(kind, 1, seen[kind], kind == EXPERTS,
                                   _CARRIES[kind]))
            seen[kind] += 1
        return out

    def router(self) -> Router:
        return Router(experts=self.n_routed_experts, top_k=self.top_k,
                      renormalise=self.norm_topk_prob,
                      scale=self.routed_scale, score="sigmoid")


PRESETS = {
    # Toy widths for the CPU tests: all three kinds, two M layers in a row
    # (one scan of two) with M layers on both sides of other segments (a
    # state layer's index among its kind is not its segment's), two
    # attention layers, half of 16 experts held so tokens route to absent
    # experts too, 2 heads a group in both mixers.
    "debug": NemotronHConfig(
        vocab_size=128, dim=64, n_layers=9, pattern="MEMM*EM*E", n_heads=4,
        n_kv_heads=2, head_dim=16, mamba_heads=8, mamba_head_dim=8,
        ssm_groups=4, ssm_state=16, chunk_size=8, latent=32, expert_dim=24,
        shared_dim=48, n_routed_experts=16, experts_held=(0, 8), top_k=3,
        max_seq_len=1024, dtype=jnp.float32),
}

# Leaves that stay float32 whatever the compute dtype is: the norms, the
# selection bias and what the state-space update reads beside its
# projections.
FLOAT32_LEAVES = ("norm", "final_norm", "gnorm", "conv_b", "dt_bias",
                  "A_log", "D", "bias")
# float32 leaves drawn N(0, std^2): the convolution's bias and the router's
# selection bias are data, and zeros would test nothing. ``ssm`` leaves
# are drawn again by ``_ssm_scalars``.
_FLOAT32_STD = {"conv_b": 0.02, "bias": 0.1, "ssm": 1.0}


def _shapes(c: NemotronHConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, fan_in)`` (``moe_decode.init_leaves``). A
    segment's layers are stacked on a leading axis."""
    e, di, h = c.dim, c.d_inner, c.mamba_heads
    q = c.n_heads * c.head_dim

    def segment(seg: Segment):
        n = seg.layers
        norm = {"norm": ((n, e), None)}
        if seg.kind == MAMBA:
            return {**norm,
                    # [z | xBC | dt] side by side (a layout).
                    "in_proj": ((n, e, di + c.conv_dim + h), e),
                    "conv_w": ((n, c.d_conv, c.conv_dim), c.d_conv),
                    "conv_b": ((n, c.conv_dim), "conv_b"),
                    "dt_bias": ((n, h), "ssm"), "A_log": ((n, h), "ssm"),
                    "D": ((n, h), "ssm"), "gnorm": ((n, di), None),
                    "out_proj": ((n, di, e), di)}
        if seg.kind == FULL:
            return {**norm, "wq": ((n, e, q), e),
                    "wkv": ((n, e, 2 * c.kv_width), e),
                    "wo": ((n, c.n_heads, c.head_dim, e), q)}
        return {**norm, "router": ((n, e, c.n_routed_experts), e),
                "bias": ((n, c.n_routed_experts), "bias"),
                "w1": ((n, e, c.latent), e), "w2": ((n, c.latent, e),
                                                   c.latent),
                "experts": {
                    "w_up": ((n, c.held[1], c.latent, c.expert_dim),
                             c.latent),
                    "w_down": ((n, c.held[1], c.expert_dim, c.latent),
                               c.expert_dim)},
                "shared": {"w_up": ((n, e, c.shared_dim), e),
                           "w_down": ((n, c.shared_dim, e), c.shared_dim)}}

    return {
        # Rows of N(0, 4 / dim) under an untied head of N(0, 1 / dim)
        # (``brumby.py``): logits of spread ~1 that every layer moves.
        "tok_embed": ((c.vocab_size, e), e / 4.0),
        "segments": [segment(s) for s in c.segments()],
        "final_norm": ((e,), None),
        "head": ((e, c.vocab_size), e),
    }


def _ssm_scalars(key: jax.Array, shape) -> Dict[str, jax.Array]:
    """What Mamba-2 initialises by rule, a head: ``A`` uniform in 1 .. 16,
    ``dt_bias`` the inverse softplus of a step log-uniform in
    ``time_step_min`` .. ``time_step_max`` = 1e-3 .. 1e-1 (these only seed
    it: nothing clamps ``dt``), ``D`` round one. Zeros would test
    nothing."""
    ka, kd, kk = jax.random.split(key, 3)
    dt = jnp.exp(jax.random.uniform(kd, shape, jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return {"A_log": jnp.log(jax.random.uniform(ka, shape, jnp.float32, 1.0,
                                                16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": 1.0 + 0.1 * jax.random.normal(kk, shape, jnp.float32)}


def init_params(config: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made leaf by leaf in ``config.dtype``
    (``moe_decode.init_leaves``): a float32 tree of the served cut would be
    18.6 GB and never exists."""
    params = moe_decode.init_leaves(_shapes(config), key, config.dtype,
                                    _FLOAT32_STD)
    for i, (seg, leaves) in enumerate(zip(config.segments(),
                                          params["segments"])):
        if seg.kind == MAMBA:
            leaves.update(_ssm_scalars(jax.random.fold_in(key, 7919 + i),
                                       leaves["A_log"].shape))
    return params


def param_count(config: NemotronHConfig) -> int:
    return moe_decode.count_leaves(_shapes(config))
