"""How a configuration file of the ViT family maps onto
``ray_tpu.models.vit``. Keys are those of the published ``config.json``."""

from __future__ import annotations

from typing import Any, Dict

from benchmarks import flops
from benchmarks.reference import vit_ref


def model_config(m: Dict, flags: Dict = None):
    from ray_tpu.models import vit

    return vit.ViTConfig(
        image_size=m["image_size"], patch_size=m["patch_size"],
        channels=m["num_channels"], num_classes=m["num_labels"],
        dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], mlp_dim=m["intermediate_size"],
        **(flags or {}))


class Train:
    def __init__(self, m: Dict, job: Dict, flags: Dict):
        from ray_tpu.models import vit

        self.m, self.vit = m, vit
        self.cfg = model_config(m, flags)
        self.items = int(job["microbatch"]) * int(job.get("accum", 1))
        self.tokens_per_item = flops.vit_tokens(m)

    def init(self, key):
        import jax

        # The program zero-initialises the classifier head, which makes
        # every logit 0 and the first loss ln(classes) whatever the trunk
        # computes; a seeded head makes the reference check say something.
        params = self.vit.init_params(self.cfg, key)
        head = jax.random.normal(jax.random.fold_in(key, 1),
                                 params["head"].shape) * 0.02
        return dict(params, head=head)

    def axes(self):
        return self.vit.param_axes(self.cfg)

    def loss(self, params, batch):
        return self.vit.loss_fn(params, batch, self.cfg)[0]

    def make_batch(self, key, items: int) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        k1, k2 = jax.random.split(key)
        c = self.cfg
        return {"images": jax.random.normal(
                    k1, (items, c.image_size, c.image_size, c.channels),
                    jnp.bfloat16),
                "labels": jax.random.randint(k2, (items,), 0, c.num_classes,
                                             jnp.int32)}

    def reference_loss(self, params, batch):
        return vit_ref.loss(params, batch["images"], batch["labels"],
                            self.cfg)

    def flops_per_token(self) -> float:
        return flops.vit_train_flops_per_token(self.m)
