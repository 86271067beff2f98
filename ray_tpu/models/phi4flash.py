"""Phi-4-mini-flash-reasoning (Microsoft, ``model_type`` ``phi4flash``): the
SambaY decoder-hybrid-decoder (arXiv:2507.06607) with differential
attention (arXiv:2410.05258). ``n_layers`` layers (32 published), every one
``h += mixer(LN(h)); h += W2(silu(g) * u)`` with ``[g | u] = W1 LN(h)``,
LayerNorm with scale and bias, a tied embedding and NO positional term.
With ``half = n_layers / 2`` the mixer of layer ``l`` is

* ``l < half``, even: a Mamba-1 selective scan (arXiv:2312.00752), which
  keeps a STATE a sequence: ``d_state`` x ``d_inner`` float32 and the last
  ``d_conv - 1`` inputs of its convolution;
* ``l < half``, odd: differential attention over the last ``window``
  tokens (its keys and values are a WINDOW kind of page);
* ``l == half``: a Mamba layer that also hands on its scan output ``m``;
* ``l == half + 1``: differential attention over everything; its keys and
  values are THE cache (YOCO, arXiv:2405.05254; a FULL kind of page);
* ``l > half + 1``, even: a gated memory unit, ``W_out(silu(W_in h) * m)``;
* ``l > half + 1``, odd: cross-attention, its own queries over the keys
  and values of layer ``half + 1``.

Differential attention: ``n_heads`` query heads are ``n_heads / 2`` PAIRS,
``n_kv_heads`` key-value heads ``n_kv_heads / 2`` pairs; a query pair ``i``
reads key-value pair ``i // (n_heads / n_kv_heads)``; with ``V = [v1 | v2]``
``o = RMSNorm(softmax(q1 k1) V - lam softmax(q2 k2) V) (1 - lam0)``, ``lam =
exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``.

This module is the model's data: its configuration and its weights. The
programs the decode engine runs are in ``phi4flash_decode.py``; the model
is served only. Layers of one mixer are stacked on a leading axis (the
Mamba layer ``half`` and the full layer ``half + 1`` stand alone), so the
programs run one ``scan`` over the (Mamba, window) pairs and one over the
(GMU, cross) pairs."""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    mlp_dim: int = 10240
    window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError(
                f"phi4flash: {self.n_layers} layers are not a whole number "
                f"of (Mamba, attention) pairs round a middle of two")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                f"phi4flash: {self.n_heads} query and {self.n_kv_heads} "
                f"key-value heads do not pair up")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.dim / 16)

    @property
    def half(self) -> int:
        return self.n_layers // 2

    @property
    def front_pairs(self) -> int:
        """(Mamba, window attention) pairs: layers ``0 .. half - 1``."""
        return self.half // 2

    @property
    def back_pairs(self) -> int:
        """(GMU, cross-attention) pairs: layers ``half + 2 ..``."""
        return self.half // 2 - 1

    @property
    def q_pairs(self) -> int:
        return self.n_heads // 2

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def kv_width(self) -> int:
        """Numbers a token's keys (and its values) take in a layer."""
        return self.n_kv_heads * self.head_dim

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def lam0(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    def kinds(self):
        """The mixer of every layer, by name."""
        h = self.half
        return tuple(
            ("mamba" if l % 2 == 0 else "window") if l < h
            else "mamba" if l == h else "full" if l == h + 1
            else ("gmu" if l % 2 == 0 else "cross")
            for l in range(self.n_layers))


PRESETS = {
    # Toy widths for the CPU tests: every kind of layer (two (Mamba,
    # window) pairs, the middle two, one (GMU, cross) pair), a window of 12
    # that pages of 4 and 8 cross.
    "debug": Phi4FlashConfig(
        vocab_size=128, dim=64, n_layers=8, n_heads=8, n_kv_heads=4,
        mlp_dim=96, window=12, d_state=4, max_seq_len=1024,
        dtype=jnp.float32),
}

# Leaves that stay float32 whatever the compute dtype is: the norms, the
# differential attention's four vectors, and what the selective scan reads
# beside its projections.
FLOAT32_LEAVES = ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "final_ln_w",
                  "final_ln_b", "lam", "subln", "conv_b", "dt_bias",
                  "A_log", "D")


def _shapes(c: Phi4FlashConfig) -> Dict[str, Any]:
    """Every leaf as ``(shape, how)``: a number draws ``N(0, 1 / how)`` in
    the compute dtype; ``"ones"`` / ``"zeros"`` are a norm's scale and
    bias; ``"bias"`` draws float32 ``N(0, 0.02^2)``; ``"lam"`` ``N(0,
    0.1^2)`` as published; ``"A_log"`` is ``log(1 .. d_state)`` a channel,
    ``"dt_bias"`` the inverse softplus of a step log-uniform in 1e-3 ..
    1e-1 (both as Mamba initialises them: zeros would test nothing)."""
    e, f, di, n, r = c.dim, c.mlp_dim, c.d_inner, c.d_state, c.dt_rank
    kv, pair = c.kv_width, 2 * c.head_dim

    def block(lead, mixer):
        return {"ln1_w": (lead + (e,), "ones"),
                "ln1_b": (lead + (e,), "zeros"), **mixer,
                "ln2_w": (lead + (e,), "ones"),
                "ln2_b": (lead + (e,), "zeros"),
                "w1": (lead + (e, 2 * f), e), "w2": (lead + (f, e), f)}

    def mamba(lead):
        return block(lead, {
            "in_proj": (lead + (e, 2 * di), e),
            "conv_w": (lead + (c.d_conv, di), c.d_conv),
            "conv_b": (lead + (di,), "bias"),
            "x_proj": (lead + (di, r + 2 * n), di),
            "dt_proj": (lead + (r, di), r),
            "dt_bias": (lead + (di,), "dt_bias"),
            "A_log": (lead + (n, di), "A_log"),
            "D": (lead + (di,), "ones"),
            "out_proj": (lead + (di, e), di)})

    def attention(lead, width):
        return block(lead, {
            "wqkv": (lead + (e, width), e),
            "bqkv": (lead + (width,), "bias"),
            "lam": (lead + (4, c.head_dim), "lam"),
            "subln": (lead + (pair,), "ones"),
            "o_proj": (lead + (e, e), e), "o_bias": (lead + (e,), "bias")})

    def gmu(lead):
        return block(lead, {"in_proj": (lead + (e, di), e),
                            "out_proj": (lead + (di, e), di)})

    return {
        # The embedding is the head too (tied): rows of N(0, 4 / dim) give
        # logits of a few units, and the current token's own row does not
        # outweigh what 32 layers add to the stream (rows of N(0, 1) would
        # make every position answer its own token, whatever the layers
        # compute and in whatever precision).
        "tok_embed": ((c.vocab_size, e), e / 4.0),
        "mamba": mamba((c.front_pairs,)),
        "window": attention((c.front_pairs,), e + 2 * kv),
        "mamba_mid": mamba(()),
        "full": attention((), e + 2 * kv),
        "gmu": gmu((c.back_pairs,)),
        "cross": attention((c.back_pairs,), e),      # queries only
        "final_ln_w": ((e,), "ones"), "final_ln_b": ((e,), "zeros"),
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(config: Phi4FlashConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, made LEAF BY LEAF in ``config.dtype``, a
    stacked leaf one layer at a time (``deepseek.init_params``): a float32
    tree of the model would be 15.4 GB and never exists."""
    dtype = jnp.dtype(config.dtype)

    def leaf(path, spec):
        shape, how = spec
        if how in ("ones", "zeros"):
            return getattr(jnp, how)(shape, jnp.float32)
        if how == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape)
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) % (2 ** 31))
        if how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(1e-1) - math.log(1e-3))
                         + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))
        if how in ("bias", "lam"):
            return jax.random.normal(k, shape, jnp.float32) * (
                0.1 if how == "lam" else 0.02)
        scale = float(how) ** -0.5

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
                       if isinstance(sub, dict) else leaf(prefix + name, sub))
                for name, sub in tree.items()}

    return walk(_shapes(config), "")


def param_count(config: Phi4FlashConfig) -> int:
    return sum(math.prod(spec[0]) for spec in jax.tree.leaves(
        _shapes(config), is_leaf=_is_spec))
