"""How a configuration file of the Phi-4-flash family maps onto the program:
``ray_tpu.models.phi4flash`` behind ``Phi4FlashDecodeDeployment``. ``Serve``
only: the model is served (two kinds of page, a recurrent state a slot),
and a train cell on it fails at once. The file holds the keys of the
published ``config.json`` at its TOP level under their published names and,
under ``assumed``, the state-space sizes that file does not carry."""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import phi4flash_ref

# ``correct`` holds a run's served tokens to TWO limits, as
# ``families/mimo_v2.py`` does and for its reasons: a margin is how far a
# served token's reference logit lies below its position's maximum; with
# random weights the top two of 200,064 logits (a spread of ~2.5) lie ~0.2
# apart, so equality of tokens cannot be asked; the replica computes in
# bfloat16 (float32 residual stream, softmax, scan and accumulation), the
# reference in float32, and 32 layers of bfloat16 matmuls move a logit by
# 0.05-0.3. There is no router here, so no flips: a sound run's margins
# are rounding alone, and the largest SOUND margin is what the second
# limit is set from. Readings on the TPU v5e (PR 45, my chip runs, 256
# served tokens a run; the sound ones are the cell's own runs, the
# control's ``benchmarks/control.py --bits 8`` on seeds 4500000211-13):
#
#   tokens that leave the reference's choice: sound 14-27 of 256 in each of
#     seventeen runs on fourteen seeds (4500000201-02; from ``git archive``
#     of the tree 4500000301-06 and, in the review round, 4500000501-06
#     with 301 and 502 run again), int8 control 84, 90, 100;
#   the largest margin: sound 0.1158-0.2755 (0.1484, 0.2755, 0.1692, 0.1840,
#     0.2146, 0.1652, 0.1196, 0.1240; 0.2514, 0.1158, 0.1937, 0.1691,
#     0.1720, 0.1415, 0.1384, 0.1691, 0.1158), control 0.8637, 1.0874,
#     1.1376;
#   the 5th largest: sound 0.0588-0.1464 (0.0795, 0.0984, 0.1001, 0.1239,
#     0.1313, 0.1074, 0.0687, 0.0729; 0.1464, 0.0645, 0.0989, 0.0772,
#     0.0588, 0.0975, 0.0695, 0.0714, 0.0645), control 0.6700, 0.8051,
#     0.8783.
#
# (With embedding rows of N(0, 1) every margin read 0.0000, sound and int8
# alike: a position's own token outweighed all 32 layers in the tied head,
# so the check tested nothing. The rows are N(0, 4 / dim) since.)
#
# 1. The RANK-th largest margin of a run is the limit that tells int8 from
#    sound: a lower precision shifts EVERY token's logits and changes the
#    choice wherever the top two lie closer than the shift (84-100 tokens,
#    by up to 1.1), a sound run changes it at ~20, by under 0.3. RANK 5,
#    limit 0.3: twice the largest sound reading of seventeen (0.1464),
#    under half the smallest control reading (0.6700).
# 2. The LARGEST margin of a run, limit 0.5, between its two readings too:
#    1.8 times the largest sound reading of seventeen (0.2755), 0.58 of the
#    smallest control reading (0.8637), so the control fails this limit as
#    well as the first. It is there for what puts fewer than RANK tokens
#    wrong: one slot's state not carried for a few tokens, one page read
#    wrong once. A NaN, a wrong page, a wrong position or a window off by
#    one reads ~10 here (a random token under a maximum of ~+11).
LARGEST_LIMIT = 0.5
RANK = 5
RANKED_LIMIT = 0.3


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
    print(f"[phi4flash] served-token margins: largest {largest:.4f} "
          f"(limit {LARGEST_LIMIT}), rank {RANK} {ranked:.4f} (limit "
          f"{RANKED_LIMIT}); {len(off)} of {len(margins)} tokens leave the "
          f"reference's choice, by {[round(m, 4) for m in off[:2 * RANK]]}",
          flush=True)
    return [largest / LARGEST_LIMIT, ranked / RANKED_LIMIT]


def model_config(config: Dict):
    """The program's config from the file's published keys (top level) and
    the sizes it ``assumed``."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models.phi4flash import Phi4FlashConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family phi4flash needs ray_tpu.models.phi4flash "
                         f"and this checkout has none ({e})") from None

    m, a = config, config["assumed"]
    expect = {"model_type": "phi4flash", "mb_per_layer": 2,
              "hidden_act": "silu", "tie_word_embeddings": True,
              "mlp_bias": False, "lm_head_bias": False, "embd_pdrop": 0,
              "resid_pdrop": 0}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"phi4flash: {key}={m[key]!r} is not "
                             f"implemented (only {want!r})")
    if a["dt_rank"] != math.ceil(m["hidden_size"] / 16):
        raise ValueError(f"phi4flash: dt_rank {a['dt_rank']} is not "
                         f"ceil(hidden_size / 16)")
    return Phi4FlashConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], mlp_dim=m["intermediate_size"],
        window=m["sliding_window"], d_state=a["d_state"], d_conv=a["d_conv"],
        expand=a["expand"], norm_eps=m["layer_norm_eps"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16)


class Serve:
    """What a serve cell needs of this family."""

    reference = "phi4flash_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import Phi4FlashDecodeDeployment

        return Phi4FlashDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(phi4flash_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast answers one token
        after each of the last ``n`` cuts of every prompt; their margins
        under the unrounded reference, as ``shares_of_limits``."""
        import jax

        from ray_tpu.models import phi4flash

        params = phi4flash.init_params(self.model_cfg, jax.random.key(seed))
        return shares_of_limits(phi4flash_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
