"""Train configuration dataclasses.

Analogue of the reference's typed config surface
(``python/ray/air/config.py``: ``ScalingConfig`` :95, ``RunConfig``,
``FailureConfig`` :395, ``CheckpointConfig``), adapted to TPU scheduling:
``resources_per_worker`` defaults to TPU chips and ``placement_strategy``
defaults to STRICT_SPREAD — one worker per TPU-VM host of a slice is the
canonical layout (one jax process per host, mesh over ICI).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class ScalingConfig:
    num_workers: int = 1
    resources_per_worker: Dict[str, float] = field(
        default_factory=lambda: {"CPU": 1.0})
    # A worker whose lease names chips opens them BEFORE the loop function
    # runs (``TrainWorker.start``, the set-up record's ``device_init``):
    # what must precede the backend goes in the worker's ``runtime_env``.
    use_tpu: bool = False
    tpu_chips_per_worker: int = 0
    placement_strategy: str = "PACK"
    # Multi-host mesh formation (jax.distributed bootstrap across the
    # worker gang); see ray_tpu.train.jax_backend.JaxConfig.
    jax_config: Optional[Any] = None

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker)
        if self.use_tpu and self.tpu_chips_per_worker:
            res["TPU"] = float(self.tpu_chips_per_worker)
        return res


@dataclass
class FailureConfig:
    """Restart-based recovery: on any worker failure the whole group is torn
    down and relaunched from the latest reported checkpoint (reference:
    ``backend_executor.py:727`` retry loop; elasticity is intentionally out of
    scope at this snapshot, matching the reference)."""

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
