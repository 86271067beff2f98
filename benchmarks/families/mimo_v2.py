"""How a configuration file of the MiMo-V2 family maps onto the program:
``ray_tpu.models.mimo`` behind ``MimoDecodeDeployment``. ``Serve`` only: the
model is served (two kinds of page, held experts), and a train cell on it
fails at once. The file holds the keys of the published ``config.json`` at
its TOP level under their published names, the two 48-long layer lists
whole (the model reads their first ``num_hidden_layers`` entries); its
``share`` says which part of a layer this chip holds."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import mimo_v2_ref

# ``correct`` holds a run's served tokens to TWO limits, as
# ``families/deepseek_v2.py`` does and for its reasons: a margin is how far
# a served token's reference logit lies below its position's maximum; with
# random weights the top two of 19,072 logits lie ~0.2 apart, so equality
# of tokens cannot be asked; the replica computes in bfloat16 (float32
# residual stream, router and softmax), the reference in float32. Readings
# on the TPU v5e (PR 43, my chip runs, 256 served tokens a run; the sound
# ones are the cell's own runs, the control's ``benchmarks/control.py
# --bits 8`` on seeds 3900430031-33):
#
#   tokens that leave the reference's choice: sound 0-4 of 256 in each of
#     seventeen runs, int8 control 11, 13, 16;
#   the largest margin: sound 0.0000-0.0086 in fifteen runs, 0.0461 and
#     0.1211 in two (a router FLIP, below), control 0.0515-0.1066;
#   the 5th largest: sound 0.0000 in all seventeen (the 4th: 0.0014 at
#     most), control 0.0304, 0.0144, 0.0254;
#   the 16th largest (deepseek-v2's rank): control 0.0000, 0.0000, 0.0031,
#     which its limit of 0.015 passes: seven layers and 16 held experts of
#     256 leave int8 fewer tokens to move than deepseek-v2's cut does.
#
# 1. The RANK-th largest margin of a run is the limit that tells int8 from
#    sound: a lower precision shifts EVERY token's logits and changes the
#    choice wherever the top two lie closer than the shift (11-16 tokens),
#    a sound run changes it at 0-4, all but a flip by under 0.009. RANK 5,
#    limit 0.005: a third of the weakest control run's reading, and a
#    sound run would need five tokens past it where none of seventeen had
#    five tokens off at all and their 4th largest stayed under 0.0015.
# 2. The LARGEST margin of a run, 1.0: not there to tell int8 (0.05-0.11)
#    from sound; it catches a NaN and tokens from a wrong page, a wrong
#    position or a window off by one (~4, as a random token reads) where
#    fewer than RANK are wrong. A router FLIP (a token whose 8th and 9th
#    ``g + b`` swap under bfloat16 noise) moves a hidden state by one held
#    expert's renormalised share, ~1/8 of a layer's routed output: two of
#    seventeen sound runs read one (0.0461, 0.1211: as large as int8's
#    largest, which is why this limit cannot be the one that tells them
#    apart), and deepseek-v2's (x 16, not renormalised) read up to 1.3,
#    hence the room.
LARGEST_LIMIT = 1.0
RANK = 5
RANKED_LIMIT = 0.005


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
    print(f"[mimo_v2] served-token margins: largest {largest:.4f} "
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
        from ray_tpu.models.mimo import MimoConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family mimo_v2 needs ray_tpu.models.mimo and "
                         f"this checkout has none ({e})") from None

    m, share = config, config["share"]
    expect = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "n_group": 1, "topk_group": 1, "n_shared_experts": None,
              "hidden_act": "silu", "attention_bias": False,
              "tie_word_embeddings": False,
              "swa_num_attention_heads": m["num_attention_heads"],
              "swa_head_dim": m["head_dim"],
              "swa_v_head_dim": m["v_head_dim"],
              "sliding_window_size": m["sliding_window"]}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"mimo_v2: {key}={m[key]!r} is not "
                             f"implemented (only {want!r})")
    if m["rope_scaling"]["type"] != "default":
        raise ValueError(f"mimo_v2: rope_scaling {m['rope_scaling']!r}")
    n = m["num_hidden_layers"]
    return MimoConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=n,
        n_heads=m["num_attention_heads"], head_dim=m["head_dim"],
        v_head_dim=m["v_head_dim"], n_kv_heads=m["num_key_value_heads"],
        swa_n_kv_heads=m["swa_num_key_value_heads"],
        rotary_dim=int(m["head_dim"] * m["partial_rotary_factor"]),
        rope_theta=float(m["rope_theta"]),
        swa_rope_theta=float(m["swa_rope_theta"]),
        window=m["sliding_window"],
        value_scale=float(m["attention_value_scale"]),
        swa_sink=m["add_swa_attention_sink_bias"],
        full_sink=m["add_full_attention_sink_bias"],
        layer_pattern=tuple(m["hybrid_layer_pattern"][:n]),
        moe_pattern=tuple(m["moe_layer_freq"][:n]),
        mlp_dim=m["intermediate_size"],
        moe_mlp_dim=m["moe_intermediate_size"],
        # The router keeps its published width; the file's count is what
        # this chip holds of it.
        n_routed_experts=share["published"]["n_routed_experts"],
        experts_held=(share["first_expert"], m["n_routed_experts"]),
        top_k=m["num_experts_per_tok"], norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=float(m["routed_scaling_factor"] or 1.0),
        norm_eps=m["layernorm_epsilon"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16)


class Serve:
    """What a serve cell needs of this family."""

    reference = "mimo_v2_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import MimoDecodeDeployment

        return MimoDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(mimo_v2_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast answers one token
        after each of the last ``n`` cuts of every prompt
        (``mimo_v2_ref.cut_prompt_margins``); their margins under the
        unrounded reference, as ``shares_of_limits``."""
        import jax

        from ray_tpu.models import mimo

        params = mimo.init_params(self.model_cfg, jax.random.key(seed))
        return shares_of_limits(mimo_v2_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
