"""How a configuration file of the Cohere2-MoE family (Command A+) maps
onto the program: ``ray_tpu.models.cohere2_moe`` behind
``Cohere2MoeDecodeDeployment``. ``Serve`` only: the model is served (two
kinds of page, held experts beside averaged shared ones), and a train cell
on it fails at once. The file holds the keys of the published
``config.json`` at its TOP level under their published names, the 32-long
``layer_types`` whole (the model reads its first ``num_hidden_layers``
entries); its ``share`` says which part of a layer this chip holds."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import cohere2_moe_ref

# ``correct`` holds a run's served tokens to TWO limits, as
# ``families/mimo_v2.py`` does and for its reasons: a margin is how far a
# served token's reference logit lies below its position's maximum; with
# random weights the top two of 32,768 logits lie ~0.4 apart, so equality
# of tokens cannot be asked; the replica computes in bfloat16 (float32
# residual stream, LayerNorm, router and softmax), the reference in
# float32. Readings on the TPU v5e (PR 49, my chip runs, 256 served tokens
# a run; the sound ones are the cell's own runs: calls A and B, seeds
# 4900000101-105, and from ``git archive`` of the tree call C, seeds
# 4900000301-308; the control's ``benchmarks/control.py --bits 8`` on
# seeds 4900000201-203, call B):
#
#   tokens that leave the reference's choice: sound 0-3 of 256 in each of
#     thirteen runs (0 in eight, 1 in four, 3 in one), int8 control 40, 33,
#     27;
#   the largest margin: sound 0.0000-0.0213 in eleven runs, 0.3814 and
#     0.4624 in two (router FLIPS, below; the first with a second of 0.2516
#     behind it), control 2.0962, 1.0819, 1.2875;
#   the 5th largest: sound 0.0000 in all thirteen (the 3rd: 0.0049 at
#     most), control 0.5340, 0.4191, 0.4800.
#
# 1. The RANK-th largest margin of a run is the limit that tells int8 from
#    sound: a lower precision shifts EVERY token's logits and changes the
#    choice wherever the top two lie closer than the shift (27-40 tokens,
#    by up to 2.1), a sound run changes it at 0-3, all but a flip by under
#    0.03. RANK 5, limit 0.05: ten times the largest sound 3rd-largest
#    reading (a sound run would need five tokens past it where none of
#    thirteen had five tokens off at all), an eighth of the weakest control
#    reading.
# 2. The LARGEST margin of a run, 1.0: between its two readings as well
#    (2.2 times the largest sound reading, under the control's smallest,
#    1.0819), but narrowly, and not the limit that tells them apart; it is
#    there for what puts fewer than RANK tokens wrong: a NaN, a token from a
#    wrong page or a wrong position, a window off by one, a window page
#    released a step early (a random token reads ~8 here: logits of
#    spread ~2 under a maximum of ~+8). It has room for a router FLIP: a
#    token whose 8th and 9th sigmoid score swap under bfloat16 noise moves a
#    hidden state by one held expert's renormalised share, ~1/8 of a
#    layer's routed output (two sound runs of thirteen read one: 0.3814
#    with 0.2516 behind it, and 0.4624; mimo-v2.5's read up to 0.12,
#    deepseek-v2's up to 1.3).
LARGEST_LIMIT = 1.0
RANK = 5
RANKED_LIMIT = 0.05


def readings(margins: List[float]) -> Tuple[float, float]:
    """(the largest, the ``RANK``-th largest) of a run's margins."""
    top = sorted(margins, reverse=True)
    return top[0], top[min(RANK, len(top)) - 1]


def shares_of_limits(margins: List[float]) -> List[float]:
    """Each of the two readings as a share of its limit: the harness
    compares the largest number it is given with ONE ``tolerance``, so the
    family hands it shares and a tolerance of 1. The readings themselves
    go to the log of the process that computed them."""
    largest, ranked = readings(margins)
    off = sorted((m for m in margins if m > 0), reverse=True)
    print(f"[cohere2_moe] served-token margins: largest {largest:.4f} "
          f"(limit {LARGEST_LIMIT}), rank {RANK} {ranked:.4f} (limit "
          f"{RANKED_LIMIT}); {len(off)} of {len(margins)} tokens leave the "
          f"reference's choice, by {[round(m, 4) for m in off[:2 * RANK]]}",
          flush=True)
    return [largest / LARGEST_LIMIT, ranked / RANKED_LIMIT]


def model_config(config: Dict):
    """The program's config from the file's published keys (top level; the
    chip's counts where ``reduced`` says so) and ``share``."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models.cohere2_moe import Cohere2MoeConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family cohere2_moe needs "
                         f"ray_tpu.models.cohere2_moe and this checkout "
                         f"has none ({e})") from None

    m, share = config, config["share"]
    expect = {"expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
              "shared_expert_combination_strategy": "average",
              "position_embedding_type": "rope_gptj", "rotary_pct": 1,
              "use_parallel_block": True, "use_qk_norm": False,
              "use_gated_activation": True, "hidden_act": "silu",
              "attention_bias": False, "tie_word_embeddings": True,
              "order_of_interleaved_layers": "local_attn_first"}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"cohere2_moe: {key}={m[key]!r} is not "
                             f"implemented (only {want!r})")
    if m["rope_parameters"]["rope_type"] != "default":
        raise ValueError(f"cohere2_moe: rope_parameters "
                         f"{m['rope_parameters']!r}")
    n = m["num_hidden_layers"]
    return Cohere2MoeConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=n,
        n_heads=m["num_attention_heads"], head_dim=m["head_dim"],
        n_kv_heads=m["num_key_value_heads"],
        rope_theta=float(m["rope_theta"]), window=m["sliding_window"],
        layer_types=tuple(m["layer_types"][:n]),
        mlp_dim=m["intermediate_size"],
        # The router keeps its published width; the file's count is what
        # this chip holds of it.
        n_routed_experts=share["published"]["num_experts"],
        experts_held=(share["first_expert"], m["num_experts"]),
        top_k=m["num_experts_per_tok"], norm_topk_prob=m["norm_topk_prob"],
        n_shared_experts=m["num_shared_experts"],
        logit_scale=float(m["logit_scale"]), norm_eps=m["layer_norm_eps"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16)


class Serve:
    """What a serve cell needs of this family."""

    reference = "cohere2_moe_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import Cohere2MoeDecodeDeployment

        return Cohere2MoeDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(cohere2_moe_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast answers one token
        after each of the last ``n`` cuts of every prompt
        (``cohere2_moe_ref.cut_prompt_margins``); their margins under the
        unrounded reference, as ``shares_of_limits``."""
        import jax

        from ray_tpu.models import cohere2_moe

        params = cohere2_moe.init_params(self.model_cfg,
                                         jax.random.key(seed))
        return shares_of_limits(cohere2_moe_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
