"""Brumby-14B-Base (Manifest AI, ``model_type`` ``brumby``): a dense
pre-norm decoder retrained from a Qwen3-14B block in which EVERY layer's
mixer is a power-retention layer (arXiv:2507.04239): there is no attention
layer and no key-value cache anywhere in the model. A layer, for input
``x`` (RMSNorm, eps 1e-6):

* ``u = RMSNorm(x)``; ``q = W_q u`` (``n_heads`` x ``head_dim``), ``k = W_k
  u``, ``v = W_v u`` (``n_kv_heads`` x ``head_dim``), no biases; an RMSNorm
  over each head of ``q`` and of ``k``; rotary over the whole head
  (half-split pairs) on both; a gate a key-value head ``gam = log
  sigmoid(W_g u)``, no bias (the seeded weights give ``W_g`` a row that
  reads a constant channel of the stream, ``_gate_channel`` below);
* power retention of degree 2 with ``s = head_dim ** -0.5``
  (``ops/power_retention.py``): ``a[t, i] = (s q_t . k_i)^2 exp(gam_{i+1} +
  ... + gam_t)``, ``o_t = sum_i a[t, i] v_i / (sum_i a[t, i] + eps)``; a
  sequence's whole cache is the state ``S`` (``head_dim`` x ``R``) and ``z``
  (``R``) a key-value head a layer, float32, whatever its length;
* ``h = x + W_o [o^1 .. o^H]``; ``y = h + W_down(silu(W_gate RMSNorm(h)) *
  W_up RMSNorm(h))``.

Untied embedding and head. This module is the model's data: its
configuration and its weights. The programs the decode engine runs are in
``brumby_decode.py``; the model is served only. The layers are stacked on a
leading axis and the programs run one ``scan`` over them."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import moe_decode
from ray_tpu.ops import power_retention


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    degree: int = 2
    phi_block: int = power_retention.BLOCK
    retention_eps: float = power_retention.EPS
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.degree != 2:
            raise ValueError(
                f"brumby: power retention of degree {self.degree} is not "
                f"implemented (only 2: the state holds the symmetric second "
                f"power)")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"brumby: {self.n_heads} query heads are not whole groups "
                f"over {self.n_kv_heads} key-value heads")

    @property
    def group(self) -> int:
        """Query heads that read one key-value head's state."""
        return self.n_heads // self.n_kv_heads

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def state_rows(self) -> int:
        """Rows ``R`` of the expansion as the state holds it."""
        return power_retention.phi_rows(self.head_dim, self.phi_block)

    @property
    def published_state_rows(self) -> int:
        """The distinct monomials of degree 2: what the mechanism needs."""
        return self.head_dim * (self.head_dim + 1) // 2

    @property
    def inv_freq(self) -> jax.Array:
        d = self.head_dim
        return 1.0 / (self.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d))


PRESETS = {
    # Toy widths for the CPU tests: three layers, a group of 3 query heads
    # a key-value head, a head of 16 in blocks of 4 (10 pairs, 160 rows for
    # 136 monomials).
    "debug": BrumbyConfig(
        vocab_size=128, dim=48, n_layers=3, n_heads=6, n_kv_heads=2,
        head_dim=16, mlp_dim=96, max_seq_len=1024, phi_block=4,
        dtype=jnp.float32),
}

# The gate of seeded weights. As published ``W_g u`` has no bias, and a
# ``W_g`` of zero-mean draws would centre sigmoid(W_g u) on 0.5: a state
# that forgets within two tokens, and a check that tests no memory (a gate of
# 1 everywhere would test no decay). A trained stream carries channels that
# hold one value at every position, under norm scales that single them out;
# the seeded weights make one (``_gate_channel``): the LAST coordinate of
# the stream is GATE_CHANNEL in every embedding row and no layer writes to
# it; ``norm1``'s scale there brings it to GATE_NORMED at the layer's
# expected rms, and ``W_g``'s row for it is GATE_LOGIT / GATE_NORMED. So
# ``W_g u`` = GATE_LOGIT x (the layer's expected rms / the position's own)
# + N(0, GATE_STD^2) from the other rows: sigmoid(4.5 +- 2 x 1.2) runs from
# 0.89 to 0.999, a state forgets over tens to a thousand tokens. The
# PROGRAM is the published layer; only the weights know.
GATE_CHANNEL = 0.125
GATE_NORMED = 8.0
GATE_LOGIT = 4.5
GATE_STD = 1.2
# The stream's mean square before layer l under these draws: the embedding's
# (4 + GATE_CHANNEL^2) / dim, 0.5 more after the first layer and 1.0 a layer
# after it (the feed-forward's E[silu(a)^2] E[b^2] = 0.355 for unit normal
# a, b through a ``W_down`` of 1 / fan_in, the rest the retention's averages
# of values through ``W_o``; read on the CPU over 8 layers at widths of 512
# and 1,280: 0.48-0.54, 1.38-1.61, 2.37-2.68 ... 6.90-7.37). At the served
# widths the stream grows by 0.75 a layer (0.41, 0.99, 1.65 ... 4.87 on the
# chip, PR 52), so the channel reads 8.9-9.9 there, ``W_g u`` 4.5-6.0 +-
# 1.1, and the gates lie at p1 0.918, p5 0.966, p50 0.9955, p95 0.9994: the
# check's limits were read at this value (``benchmarks/families/brumby.py``).
LAYER_MEAN_SQUARE = 1.0

NORM_LEAVES = ("norm1", "norm2", "q_norm", "k_norm", "final_norm")
# Leaves that stay float32 whatever the compute dtype is.
FLOAT32_LEAVES = NORM_LEAVES


def _shapes(c: BrumbyConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, fan_in)`` (``moe_decode.init_leaves``): a
    number draws ``N(0, 1 / fan_in)`` in the compute dtype; ``None`` is a
    norm scale (ones). The layers are stacked on a leading axis."""
    n, e, f = c.n_layers, c.dim, c.mlp_dim
    h, j, d = c.n_heads, c.n_kv_heads, c.head_dim
    return {
        # Rows of N(0, 4 / dim), as ``phi4flash.py``'s.
        "tok_embed": ((c.vocab_size, e), e / 4.0),
        "layers": {
            "norm1": ((n, e), None),
            "wq": ((n, e, h * d), e),
            "wk": ((n, e, j * d), e),
            "wv": ((n, e, j * d), e),
            "wg": ((n, e, j), e / GATE_STD ** 2),
            "q_norm": ((n, d), None),
            "k_norm": ((n, d), None),
            "wo": ((n, h, d, e), h * d),
            "norm2": ((n, e), None),
            "w_gate": ((n, e, f), e),
            "w_up": ((n, e, f), e),
            "w_down": ((n, f, e), f),
        },
        "final_norm": ((e,), None),
        "lm_head": ((e, c.vocab_size), e),
    }


def init_params(config: BrumbyConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made leaf by leaf in ``config.dtype``
    (``moe_decode.init_leaves``): a float32 tree of the served cut would be
    16.8 GB and never exists."""
    return _gate_channel(
        moe_decode.init_leaves(_shapes(config), key, config.dtype), config)


def _gate_channel(params: Dict[str, Any], c: BrumbyConfig) -> Dict[str, Any]:
    """The constant channel the seeded gate reads (GATE_CHANNEL, above): the
    stream's last coordinate, set in every embedding row, written by no
    layer, scaled by ``norm1`` and read by ``W_g``. Each leaf is changed
    where it lies (donated), one column or row of it."""
    rms = jnp.sqrt((4.0 + GATE_CHANNEL ** 2) / c.dim + LAYER_MEAN_SQUARE
                   * jnp.maximum(jnp.arange(c.n_layers) - 0.5, 0.0))

    def put(leaf, at, value):
        return jax.jit(lambda w, v: w.at[at].set(v.astype(w.dtype)),
                       donate_argnums=0)(leaf, jnp.asarray(value))

    last = (Ellipsis, -1)
    layers = params["layers"]
    params["tok_embed"] = put(params["tok_embed"], last, GATE_CHANNEL)
    layers["wo"] = put(layers["wo"], last, 0.0)
    layers["w_down"] = put(layers["w_down"], last, 0.0)
    layers["norm1"] = put(layers["norm1"], last,
                          GATE_NORMED * rms / GATE_CHANNEL)
    layers["wg"] = put(layers["wg"], (slice(None), -1),
                       GATE_LOGIT / GATE_NORMED)
    return params


def param_count(config: BrumbyConfig) -> int:
    return moe_decode.count_leaves(_shapes(config))
