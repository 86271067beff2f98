"""Operations the forward and backward passes NEED per token (recompute is
not counted). Copied arithmetic: ``llama.flops_per_token`` /
``vit.flops_per_image`` of the program (and ``bench.py``, which used them)
compute the same; the benchmark keeps its own so that a PR cannot move MFU
by editing the formula."""

from __future__ import annotations

from typing import Dict


def llama_params(m: Dict) -> int:
    e, l = m["hidden_size"], m["num_hidden_layers"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d, f, v = e // h, m["intermediate_size"], m["vocab_size"]
    per_layer = e * (h + 2 * kv) * d + h * d * e + 3 * e * f + 2 * e
    head = 0 if m.get("tie_word_embeddings") else e * v
    return v * e + l * per_layer + e + head


def llama_train_flops_per_token(m: Dict, seq: int) -> float:
    """6 N for the matmuls of forward and backward, plus the attention
    scores and values: 12 * layers * heads * head_dim * seq."""
    return 6.0 * llama_params(m) + 12.0 * m["num_hidden_layers"] * \
        m["hidden_size"] * seq


def vit_tokens(m: Dict) -> int:
    return (m["image_size"] // m["patch_size"]) ** 2


def vit_train_flops_per_token(m: Dict) -> float:
    """Per PATCH token of one image: 6 x the per-token parameters (the
    pooled classifier head runs once an image, the position table does no
    matmul) plus the attention quadratic term."""
    e, l, f = m["hidden_size"], m["num_hidden_layers"], m["intermediate_size"]
    t = vit_tokens(m)
    patch_dim = m["patch_size"] ** 2 * m["num_channels"]
    per_layer = 4 * e * e + 2 * e * f + 4 * e + f + e
    per_token = patch_dim * e + l * per_layer + 2 * e
    head = e * m["num_labels"]
    return 6.0 * per_token + 6.0 * head / t + 12.0 * l * e * t
