"""How a configuration file of the Nemotron-H family maps onto the program:
``ray_tpu.models.nemotron_h`` behind ``NemotronHDecodeDeployment``. ``Serve``
only: the model is served (one kind of page beside a Mamba-2 state a slot,
held experts in a latent width), and a train cell on it fails at once. The
file holds the keys of the published ``config.json`` at its TOP level under
their published names, the 88-letter ``hybrid_override_pattern`` whole (the
model reads its first ``num_hidden_layers`` letters); its ``share`` says
which part of a layer this chip holds, its ``assumed`` what that file does
not carry."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import nemotron_h_ref

# ``correct`` holds a run's served tokens to TWO limits, as
# ``families/cohere2_moe.py`` does and for its reasons: a margin is how far
# a served token's reference logit lies below its position's maximum; with
# random weights the top two of 32,768 logits (a spread of 1) lie ~0.2
# apart, so equality of tokens cannot be asked; the replica computes in
# bfloat16 (float32 norms, router, state and accumulation; the stream itself
# bfloat16, as published), the reference in float32 by the RECURRENCE where
# the replica computes by chunks of matmuls. This model is NOISY under
# bfloat16: a fifth of a sound run's tokens leave the reference's choice.
# The expert layers make it so (computed in float32 at a smaller width on
# the CPU they cut the logits' error to a third; a float32 stream cuts it
# by 15%): 22 experts a token whose squared ReLU doubles a relative error,
# under a factor 5, and a router of top 22 of 512 whose 22nd and 23rd
# score lie ~0.005 apart, so that a flip (one held expert's 5 / 22 of a
# layer's routed sum) is no rare event but the rule. int8 weights are only
# about TWICE as noisy, so the largest margins do not tell the two apart
# (their tails are flips, in both) and the limit that does lies DEEP in
# the run's ranked margins.
# Readings on the TPU v5e (PR 57, my chip runs, 256 served tokens a run:
# the cell's own check in calls A and B2, seeds 5700000102-103 and
# 5700000201-204, and the engine alone at the cell's layout on the check's
# prompts with the whole ranked list kept, ``.stage/margins.py``, calls M
# and M2, seeds 5700000601-608; the control's int8 reference,
# ``benchmarks/control.py --bits 8`` and the same script, seeds
# 5700000501-504):
#
#   tokens that leave the reference's choice: sound 35-62 of 256 in
#     fourteen runs (42, 49, 46, 58, 58, 62; 52, 35, 38, 54, 44, 59, 47,
#     59), int8 control 101, 107, 101, 98;
#   the largest margin: sound 0.35-1.25 (0.65, 0.68, 0.62, 1.06, 1.25,
#     0.81; 0.75, 0.54, 0.47, 0.76, 0.35, 0.74, 0.94, 0.82), control 1.24,
#     1.28, 0.96, 1.09: NOT apart;
#   the 8th largest: sound 0.19-0.49, control 0.52-0.76: apart by 6%;
#   the 32nd: sound 0.010-0.118 (eight runs), control 0.297-0.395;
#   the 48th: sound 0.0000-0.0315 (0.0075, 0, 0, 0.0130, 0, 0.0315, 0,
#     0.0297), control 0.2072, 0.2336, 0.1724, 0.1679;
#   the 64th: sound 0.0000 in all, control 0.086-0.177.
#
# 1. The RANK-th largest margin of a run tells a lower precision from a
#    sound run: int8 weights shift EVERY token's logits and change the
#    choice wherever the top two lie closer than the shift (~100 tokens), a
#    sound run changes it at 35-62. RANK 48, limit 0.10: three times the
#    largest sound reading of eight (0.0315; a run would need some 70
#    tokens off, 2 sd over the fourteen's mean of 50), 0.6 of the smallest
#    control reading of four (0.1679).
# 2. The LARGEST margin of a run, limit 2.5: twice the largest sound
#    reading of fourteen (1.25, a flip's tail), and the control's lie
#    below it: the control fails the first limit, not this one. It is there
#    for what puts fewer than RANK tokens wrong: one slot's state not
#    carried across one chunk edge, one page read wrong once, a NaN, a
#    position off by one (a random token reads ~4.2 under a maximum of
#    ~+4.2 of 32,768 logits of spread 1).
LARGEST_LIMIT = 2.5
RANK = 48
RANKED_LIMIT = 0.10


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
    print(f"[nemotron_h] served-token margins: largest {largest:.4f} "
          f"(limit {LARGEST_LIMIT}), rank {RANK} {ranked:.4f} (limit "
          f"{RANKED_LIMIT}); {len(off)} of {len(margins)} tokens leave the "
          f"reference's choice, by {[round(m, 4) for m in off[:16]]}",
          flush=True)
    return [largest / LARGEST_LIMIT, ranked / RANKED_LIMIT]


def model_config(config: Dict):
    """The program's config from the file's published keys (top level; the
    chip's counts where ``reduced`` says so) and ``share``."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models.nemotron_h import NemotronHConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family nemotron_h needs "
                         f"ray_tpu.models.nemotron_h and this checkout has "
                         f"none ({e})") from None

    m, share = config, config["share"]
    expect = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
              "mamba_hidden_act": "silu", "attention_bias": False,
              "mlp_bias": False, "use_bias": False,
              "mamba_proj_bias": False, "use_conv_bias": True,
              "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
              "n_shared_experts": 1, "residual_in_fp32": False,
              "sliding_window": None, "moe_shared_expert_overlap": False}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"nemotron_h: {key}={m[key]!r} is not "
                             f"implemented (only {want!r})")
    if m["expand"] * m["hidden_size"] != \
            m["mamba_num_heads"] * m["mamba_head_dim"]:
        raise ValueError("nemotron_h: expand x hidden_size is not "
                         "mamba_num_heads x mamba_head_dim")
    if m["moe_intermediate_size"] != m["intermediate_size"] \
            or m["norm_eps"] != m["layer_norm_epsilon"]:
        raise ValueError("nemotron_h: the file's two expert widths, or its "
                         "two norm eps, differ")
    return NemotronHConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        pattern=m["hybrid_override_pattern"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        mamba_heads=m["mamba_num_heads"], mamba_head_dim=m["mamba_head_dim"],
        ssm_groups=m["n_groups"], ssm_state=m["ssm_state_size"],
        d_conv=m["conv_kernel"], chunk_size=m["chunk_size"],
        latent=m["moe_latent_size"], expert_dim=m["moe_intermediate_size"],
        shared_dim=m["moe_shared_expert_intermediate_size"],
        # The router keeps its published width; the file's count is what
        # this chip holds of it.
        n_routed_experts=share["published"]["n_routed_experts"],
        experts_held=(share["first_expert"], m["n_routed_experts"]),
        top_k=m["num_experts_per_tok"], norm_topk_prob=m["norm_topk_prob"],
        routed_scale=float(m["routed_scaling_factor"]),
        norm_eps=m["layer_norm_epsilon"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16)


class Serve:
    """What a serve cell needs of this family."""

    reference = "nemotron_h_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import NemotronHDecodeDeployment

        return NemotronHDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(nemotron_h_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast answers one token
        after each of the last ``n`` cuts of every prompt; their margins
        under the unrounded reference, as ``shares_of_limits``."""
        import jax

        from ray_tpu.models import nemotron_h

        params = nemotron_h.init_params(self.model_cfg,
                                        jax.random.key(seed))
        return shares_of_limits(nemotron_h_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
