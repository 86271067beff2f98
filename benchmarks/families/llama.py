"""How a configuration file of the decoder family maps onto the program:
``ray_tpu.models.llama`` for training, ``LlamaDecodeDeployment`` for
serving. The file's keys are those of the model's published
``config.json``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmarks import flops
from benchmarks.reference import llama_ref


def model_config(m: Dict, flags: Dict = None):
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], mlp_dim=m["intermediate_size"],
        max_seq_len=m["max_position_embeddings"],
        rope_theta=float(m["rope_theta"]), norm_eps=m["rms_norm_eps"])
    return dataclasses.replace(cfg, **(flags or {}))


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
