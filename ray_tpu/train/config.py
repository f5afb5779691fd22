"""Train configuration dataclasses.

Reference parity: python/ray/air/config.py (ScalingConfig/RunConfig/
FailureConfig/CheckpointConfig) + train/v2/api/config.py:70-104
(ScalingConfig.use_tpu/topology/accelerator_type for TPU slices).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


@dataclass
class ScalingConfig:
    """How many workers and what each one holds.

    TPU path: ``use_tpu=True`` + ``topology`` ("2x2", "4x4", ...) gang-
    reserves one slice via SlicePlacementGroup and places one worker per
    host; ``num_workers`` is then derived from the slice host count.
    """

    num_workers: int = 1
    use_tpu: bool = False
    use_gpu: bool = False  # accepted for API parity; TPU build ignores it
    topology: str | None = None
    accelerator_version: str = "v5e"
    accelerator_type: str | None = None
    resources_per_worker: dict | None = None
    placement_strategy: str = "PACK"

    def __post_init__(self):
        if self.accelerator_type and not self.use_tpu:
            self.use_tpu = self.accelerator_type.upper().startswith("TPU")

    @property
    def _worker_resources(self) -> dict:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if self.use_tpu:
            # a TPU worker is the one process that opens its chips: reserve
            # them so the scheduler never co-locates a second JAX process
            # (resources_per_worker={"TPU": n} asks for more than one)
            res.setdefault("TPU", 1.0)
        return res


@dataclass
class FailureConfig:
    """Reference: air/config.py FailureConfig; train v2 failure_handling/.

    max_failures: total worker-group restarts allowed (-1 = infinite).
    """

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    """Reference: air/config.py CheckpointConfig (top-k retention)."""

    num_to_keep: int | None = None
    checkpoint_score_attribute: str | None = None
    checkpoint_score_order: str = "max"

    def __post_init__(self):
        if self.checkpoint_score_order not in ("max", "min"):
            raise ValueError("checkpoint_score_order must be 'max' or 'min'")


@dataclass
class RunConfig:
    """Reference: air/config.py RunConfig."""

    name: str | None = None
    storage_path: str | None = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    verbose: int = 1
    # experiment-lifecycle hooks (ray_tpu.tune.callbacks; reference:
    # air RunConfig.callbacks)
    callbacks: list = field(default_factory=list)

    def __post_init__(self):
        if self.storage_path is None:
            self.storage_path = os.environ.get(
                "RT_STORAGE_PATH", os.path.expanduser("~/ray_tpu_results")
            )
        if self.name is None:
            import time

            self.name = f"train-{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid()}"
