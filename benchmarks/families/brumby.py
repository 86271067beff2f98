"""How a configuration file of the Brumby family maps onto the program:
``ray_tpu.models.brumby`` behind ``BrumbyDecodeDeployment``. ``Serve``
only: the model is served (no kind of page, a power-retention state a
slot), and a train cell on it fails at once. The file holds the keys of the
published ``config.json`` at its TOP level under their published names and,
under ``assumed``, what that file does not carry: the degree, the gate, the
normaliser's eps and the layout of the state."""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks import families
from benchmarks.reference import brumby_ref

# ``correct`` holds a run's served tokens to TWO limits, as
# ``families/phi4flash.py`` does and for its reasons: a margin is how far a
# served token's reference logit lies below its position's maximum; with
# random weights the top two of 151,936 logits (a spread of ~1) lie ~0.1
# apart, so equality of tokens cannot be asked; the replica computes in
# bfloat16 (float32 residual stream, state and accumulation), the reference
# in float32 by PAIRS where the replica computes by STATE. There is no
# router here, so no flips: a sound run's margins are rounding alone.
# Readings on the TPU v5e (PR 52, my chip runs, call R1, ``git archive`` of
# the tree, 256 served tokens a run, the gates as the seeded weights hold
# them, ``models/brumby.py::_gate_channel``; the sound
# ones are the cell's own runs on seeds 5200001201-07 and, call R3, the one
# held to these limits, 5200001401; the control's
# ``benchmarks/control.py --bits 8`` on seeds 5200001101-03):
#
#   tokens that leave the reference's choice: sound 3-10 of 256, int8
#     control 36, 32, 43;
#   the largest margin: sound 0.0038-0.0283 (0.0038, 0.0165, 0.0194,
#     0.0200, 0.0100, 0.0283, 0.0108; 0.0162), control 0.1380, 0.1901,
#     0.1825;
#   the 5th largest: sound 0.0000-0.0044 (0.0010, 0.0040, 0.0010, 0.0000,
#     0.0000, 0.0044, 0.0000; 0.0043), control 0.1045, 0.1129, 0.0985.
#
# (The margins are a tenth of phi-4-mini-flash's: an untied head of N(0, 1
# / dim) over a unit-norm stream gives logits of spread 1, not 2.5, and
# gates with a median of 0.9955 average a state over hundreds of tokens.)
#
# 1. The RANK-th largest margin of a run tells a lower precision from a
#    sound run: int8 weights shift EVERY token's logits and change the
#    choice wherever the top two lie closer than the shift (32-43 tokens,
#    by up to 0.19); a sound run changes it at 3-10, by under 0.03. RANK 5,
#    limit 0.025: 5.7 times the largest sound reading of eight (0.0044), a
#    quarter of the smallest control reading (0.0985).
# 2. The LARGEST margin of a run, limit 0.08: 2.8 times the largest sound
#    reading of eight (0.0283), 0.58 of the smallest control reading
#    (0.1380), so the control fails this limit as well as the first. It is
#    there for what puts fewer than RANK tokens wrong: one slot's state not
#    carried across one chunk edge, one tile of a state not written back
#    once. A NaN, a state of another slot or a position off by one reads
#    several units here (a random token under a maximum of ~+4.5).
LARGEST_LIMIT = 0.08
RANK = 5
RANKED_LIMIT = 0.025


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
    print(f"[brumby] served-token margins: largest {largest:.4f} "
          f"(limit {LARGEST_LIMIT}), rank {RANK} {ranked:.4f} (limit "
          f"{RANKED_LIMIT}); {len(off)} of {len(margins)} tokens leave the "
          f"reference's choice, by {[round(m, 4) for m in off[:2 * RANK]]}",
          flush=True)
    return [largest / LARGEST_LIMIT, ranked / RANKED_LIMIT]


def model_config(config: Dict):
    """The program's config from the file's published keys (top level) and
    what it ``assumed``."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models.brumby import BrumbyConfig
    except ImportError as e:
        # A checkout from before the model: the cell fails at once.
        raise ValueError(f"family brumby needs ray_tpu.models.brumby and "
                         f"this checkout has none ({e})") from None

    m, a = config, config["assumed"]
    expect = {"model_type": "brumby", "hidden_act": "silu",
              "tie_word_embeddings": False, "attention_bias": False,
              "rope_scaling": None, "use_sliding_window": False}
    for key, want in expect.items():
        if m[key] != want:
            raise ValueError(f"brumby: {key}={m[key]!r} is not implemented "
                             f"(only {want!r})")
    cfg = BrumbyConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        mlp_dim=m["intermediate_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=m["rms_norm_eps"], max_seq_len=m["max_position_embeddings"],
        degree=a["degree"], phi_block=a["phi_block"],
        retention_eps=a["retention_eps"], dtype=jnp.bfloat16)
    if a["gate_heads"] != cfg.n_kv_heads:
        raise ValueError(f"brumby: a gate of {a['gate_heads']} heads is not "
                         f"one a key-value head ({cfg.n_kv_heads})")
    held = (cfg.published_state_rows, cfg.state_rows)
    if (a["state_rows_published"], a["state_rows_held"]) != held:
        raise ValueError(
            f"brumby: the file states {a['state_rows_published']} rows of "
            f"state, {a['state_rows_held']} held; the program has {held}")
    return cfg


class Serve:
    """What a serve cell needs of this family."""

    reference = "brumby_ref"
    # Of ``shares_of_limits``: neither reading above its limit.
    tolerance = 1.0

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config)
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        from ray_tpu.serve.decode import BrumbyDecodeDeployment

        return BrumbyDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config. What
        comes back are ``shares_of_limits`` of the served tokens'
        margins."""
        return shares_of_limits(brumby_ref.served_token_margins(
            params, cfg, prompts, answers))

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``; the reference with every
        matrix rounded to ``bits`` bits as it is upcast answers one token
        after each of the last ``n`` cuts of every prompt; their margins
        under the unrounded reference, as ``shares_of_limits``."""
        import jax

        from ray_tpu.models import brumby

        params = brumby.init_params(self.model_cfg, jax.random.key(seed))
        return shares_of_limits(brumby_ref.cut_prompt_margins(
            params, self.model_cfg, prompts, n, bits))
