"""How a configuration file of the DeepSeek-V2 family maps onto the
program: ``ray_tpu.models.deepseek`` behind ``DeepseekDecodeDeployment``.
``Serve`` only: the model is served (latent cache, absorbed decode), and a
train cell on it fails at once, as a serve cell does on ``vit``. The
file holds the keys of the published ``config.json`` at its TOP level,
under their published names (the driver compares them there with the
catalog's row; the llama and vit files keep theirs in a ``model`` group);
its ``share`` says which part of a layer this chip holds."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import deepseek_v2_ref

# ``correct`` holds a run's served tokens to TWO limits. A margin is how
# far a served token's reference logit lies below its position's maximum.
# With random weights the logits are ~N(0, 1) over 25,600 entries and the
# top two lie ~0.2 apart, so equality of tokens cannot be asked. The
# replica computes in bfloat16 (float32 residual stream, router and
# softmax), the reference in float32.
#
# 1. The LARGEST margin of a run. A sound run's is set by the router, not
#    by rounding: a token whose 3rd and 4th GROUP scores (or 6th and 7th
#    expert scores) lie within what bfloat16 matmuls leave of noise in its
#    hidden state picks other experts in the replica than in the
#    reference, and that moves its hidden state by those experts' whole
#    weighted output (reproduced token for token on the v5e, PERF.md
#    section 4: group scores 6e-5 apart, median 3.7e-3; absorbed decode
#    and up-projected prefill agree with each other there). Such a FLIP
#    moves a token as far as int8 weights move their worst, so this limit
#    cannot tell int8 from sound: sound runs read 0.1722-1.1416 (six runs
#    of 256 tokens, 2-7 flips each; 1.3124 in six later ones), the int8 control 0.7237-1.4717. The
#    flips' margins fall off about exponentially (mean 0.38), so 3.0
#    leaves a run's handful of them room; it catches a NaN (4.36, the
#    undefined rows of the chip's ragged matmul, cured in ``ops/moe.py``)
#    and a few tokens from a wrong page or position (~4, as a random
#    token reads) where too few are wrong for the second limit.
# 2. The RANK-th largest margin of a run: a number that RANK - 1 flips
#    cannot move, and that a lower precision does move, because it shifts
#    EVERY token's logits, changes the choice wherever the top two lie
#    closer than the shift, and flips routers ten times as often. Of 256
#    served tokens a sound run leaves the reference's choice at 4-14, of
#    which 2-7 by more than 0.015 (flips; roundings read under 0.03); the
#    int8 control at 33-46, of which 23-40 by more than 0.015. The 16th
#    largest: sound 0.0000 in all six runs (the 12th: 0.0056 at most), the
#    control 0.0466, 0.0769, 0.1464, 0.1469, 0.1517, 0.1686 (PR 36, the
#    v5e, one set of weights and six sets of prompts each). The limit lies
#    a third of the way up: sixteen tokens over 0.015 in a sound run would
#    be three times its flips, and the weakest control run had 23.
# The check serves 64 tokens a prompt for this: with ISSUE 36's 8 (32 a
# run) a sound run leaves the choice at ~1 token and the control at ~5,
# and no count tells those apart.
LARGEST_LIMIT = 3.0
RANK = 16
RANKED_LIMIT = 0.015


def readings(margins: List[float]) -> Tuple[float, float]:
    """(the largest, the ``RANK``-th largest) of a run's margins."""
    top = sorted(margins, reverse=True)
    return top[0], top[min(RANK, len(top)) - 1]


def shares_of_limits(margins: List[float]) -> List[float]:
    """Each of the two readings as a share of its limit: the harness
    compares the largest number it is given with ONE ``tolerance``, so
    the family hands it shares and a tolerance of 1. The readings
    themselves go to the log of the process that computed them (the
    replica's reaches the run's output)."""
    largest, ranked = readings(margins)
    off = sorted((m for m in margins if m > 0), reverse=True)
    print(f"[deepseek_v2] served-token margins: largest {largest:.4f} "
          f"(limit {LARGEST_LIMIT}), rank {RANK} {ranked:.4f} (limit "
          f"{RANKED_LIMIT}); {len(off)} of {len(margins)} tokens leave the "
          f"reference's choice, by {[round(m, 4) for m in off[:2 * RANK]]}",
          flush=True)
    return [largest / LARGEST_LIMIT, ranked / RANKED_LIMIT]


def model_config(config: Dict):
    """The program's config from the file's published keys (top level; the
    chip's counts where ``reduced`` says so) and ``share`` (where the held
    experts start, and the published counts)."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models.deepseek import DeepseekConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family deepseek_v2 needs ray_tpu.models.deepseek "
                         f"and this checkout has none ({e})") from None

    m, share = config, config["share"]
    expect = {"moe_layer_freq": 1, "scoring_func": "softmax",
              "topk_method": "group_limited_greedy", "hidden_act": "silu",
              "attention_bias": False, "tie_word_embeddings": False,
              "num_key_value_heads": m["num_attention_heads"]}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"deepseek_v2: {key}={m[key]!r} is not "
                             f"implemented (only {want!r})")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"deepseek_v2: rope_scaling type {rs['type']!r}")
    return DeepseekConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        mlp_dim=m["intermediate_size"],
        moe_mlp_dim=m["moe_intermediate_size"],
        n_dense_layers=m["first_k_dense_replace"],
        # The router keeps its published width; the file's count is what
        # this chip holds of it.
        n_routed_experts=share["published"]["n_routed_experts"],
        experts_held=(share["first_expert"], m["n_routed_experts"]),
        n_shared_experts=m["n_shared_experts"],
        top_k=m["num_experts_per_tok"], n_group=m["n_group"],
        topk_group=m["topk_group"], norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max_len=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16)


class Serve:
    """What a serve cell needs of this family."""

    reference = "deepseek_v2_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import DeepseekDecodeDeployment

        return DeepseekDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(deepseek_v2_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast (a rounded copy of
        10 GB of weights would not fit beside them) answers one token
        after each of the last ``n`` cuts of every prompt
        (``deepseek_v2_ref.cut_prompt_margins``: as many tokens as the
        check serves, at the same context lengths, for two forwards a
        prompt); their margins under the unrounded reference, as
        ``shares_of_limits``."""
        import jax

        from ray_tpu.models import deepseek

        params = deepseek.init_params(self.model_cfg, jax.random.key(seed))
        return shares_of_limits(deepseek_v2_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
