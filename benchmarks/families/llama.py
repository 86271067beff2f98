"""How a configuration file of the decoder family maps onto the program:
``ray_tpu.models.llama`` for training (``Train``), ``LlamaDecodeDeployment``
for serving (``Serve``). The file's keys are those of the model's published
``config.json``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

from benchmarks import families, flops
from benchmarks.reference import llama_ref

# A served token's reference logit may lie this far below its position's
# maximum. The replica computes in bf16 (8 bits of mantissa) through 24
# layers; with random weights the logits are ~N(0, 1) over 92,544 entries
# and the top two lie ~0.05 apart, so equality of tokens cannot be asked.
# Measured on the v5e (PR 23): the largest margin of any run was 0.047; a
# replica that drops a layer or mis-places the cache lands whole units away.
LOGIT_TOLERANCE = 0.25


def model_config(m: Dict, flags: Dict = None):
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], mlp_dim=m["intermediate_size"],
        max_seq_len=m["max_position_embeddings"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"])
    return dataclasses.replace(cfg, **(flags or {}))


class Serve:
    """What a serve cell needs of this family: everything about a served
    architecture that is not traffic."""

    reference = "llama_ref"
    tolerance = LOGIT_TOLERANCE

    def __init__(self, config: Dict):
        self.model_cfg = model_config(config["model"])
        self.vocab = self.model_cfg.vocab_size
        self.check = families.serve_check(config)

    @staticmethod
    def deployment_class():
        """The program's deployment the cell serves through; it takes
        ``config=``, ``seed=`` and the keys of a ``serve.layouts`` entry."""
        from ray_tpu.serve.decode import LlamaDecodeDeployment

        return LlamaDecodeDeployment

    @staticmethod
    def reference_margins(params, cfg, prompts: List[List[int]],
                          answers: List[List[int]]) -> List[float]:
        """Runs in the replica, on its weights and its model config."""
        return llama_ref.served_token_margins(params, cfg, prompts, answers)

    def control_margins(self, seed: int, prompts: List[List[int]], n: int,
                        bits: int) -> List[float]:
        """The control of ``correct`` (``benchmarks/control.py``): weights
        as the replica makes them from ``seed``, rounded to ``bits`` bits,
        answer through the reference; the margins of those answers under
        the unrounded reference. The weights are made twice, so that only
        one copy is ever on the device."""
        import jax

        from ray_tpu.models import llama

        def weights():
            return llama.init_params(self.model_cfg, jax.random.key(seed))

        rounded = jax.jit(llama_ref.rounded_weights, static_argnums=1,
                          donate_argnums=0)(weights(), bits)
        answers = llama_ref.greedy_answers(rounded, self.model_cfg, prompts,
                                           n)
        del rounded
        return llama_ref.served_token_margins(weights(), self.model_cfg,
                                              prompts, answers)


class Train:
    """What the train loop needs of this family."""

    def __init__(self, m: Dict, job: Dict, flags: Dict):
        from ray_tpu.models import llama

        self.m, self.seq = m, int(job["seq"])
        # Training never looks past ``seq`` positions: the rotary table is
        # built for the job's length, not the model's 32k.
        self.cfg = dataclasses.replace(model_config(m, flags),
                                       max_seq_len=self.seq)
        self.llama = llama
        self.items = int(job["microbatch"]) * int(job.get("accum", 1))
        self.tokens_per_item = self.seq

    def init(self, key):
        return self.llama.init_params(self.cfg, key)

    def axes(self):
        return self.llama.param_axes(self.cfg)

    def loss(self, params, batch):
        return self.llama.loss_fn(params, batch, self.cfg)

    def make_batch(self, key, items: int) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        return {"tokens": jax.random.randint(
            key, (items, self.seq + 1), 0, self.cfg.vocab_size, jnp.int32)}

    def reference_loss(self, params, batch):
        return llama_ref.loss(params, batch["tokens"], self.cfg)

    def flops_per_token(self) -> float:
        return flops.llama_train_flops_per_token(self.m, self.seq)
