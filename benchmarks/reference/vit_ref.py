"""Plain reference of the ViT the train cell runs: float32 ``jax.numpy``,
matmuls at the highest precision, no kernels. Pre-norm blocks with
LayerNorm (eps 1e-6), multi-head attention without q/k/v bias, GELU MLP
with biases, mean pooling over the patch tokens and a linear head, as
``ray_tpu.models.vit`` defines the model (its departures from the published
checkpoint, no CLS token and no attention biases, are listed in the
configuration file)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _ln(x, scale, bias, eps=1e-6):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * scale + bias


def logits(params, images, cfg):
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        b, h, w, ch = images.shape
        ps = cfg.patch_size
        x = images.astype(jnp.float32).reshape(b, h // ps, ps, w // ps, ps,
                                               ch)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, -1, ps * ps * ch)
        x = x @ p["patch_embed"] + p["pos_embed"]

        def block(x, l):
            hn = _ln(x, l["ln1_scale"], l["ln1_bias"])
            qkv = jnp.einsum("bne,ehd->bnhd", hn, l["wqkv"])
            q, k, v = jnp.split(qkv, 3, axis=2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + jnp.einsum("bnhd,hde->bne", a, l["wo"])
            hn = _ln(x, l["ln2_scale"], l["ln2_bias"])
            up = jax.nn.gelu(hn @ l["w_up"] + l["b_up"])
            return x + up @ l["w_down"] + l["b_down"], None

        x, _ = jax.lax.scan(block, x, p["layers"])
        x = _ln(x, p["final_ln_scale"], p["final_ln_bias"])
        return jnp.mean(x, 1) @ p["head"] + p["head_bias"]


def loss(params, images, labels, cfg):
    lg = logits(params, images, cfg)
    logz = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.mean(logz - gold)
