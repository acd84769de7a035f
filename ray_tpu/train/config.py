"""Train/AIR configuration types.

Parity: ``python/ray/air/config.py`` (ScalingConfig, RunConfig,
FailureConfig, CheckpointConfig) — with TPU-first extensions: ScalingConfig
speaks mesh axes (dp/fsdp/tp/sp/ep) instead of just ``num_workers`` ×
``use_gpu``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class ScalingConfig:
    num_workers: int = 1
    # None: the workers take the cluster's TPU chips when it has any,
    # split evenly (a worker without the TPU resource is pinned to the
    # CPU by its node manager, so a default of "no" would train on the
    # CPU of a TPU host without a word).  True insists on chips — the
    # job is infeasible without them; False asks for the CPU.
    use_tpu: Optional[bool] = None
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # TPU-first: logical mesh per worker-collective (axis name -> size);
    # -1 means "fill with whatever devices the group has".
    mesh_axes: Optional[Dict[str, int]] = None

    @property
    def _resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if "TPU" in res or self.use_tpu is False:
            return res
        import ray_tpu
        chips = (ray_tpu.cluster_resources().get("TPU", 0)
                 if ray_tpu.is_initialized() else 0)
        if self.use_tpu or chips:
            res["TPU"] = float(max(1, int(chips // self.num_workers)))
        return res

    def as_placement_group_factory(self):
        from ray_tpu.util.placement_group import placement_group
        bundles = [self._resources for _ in range(self.num_workers)]
        return lambda: placement_group(bundles,
                                       strategy=self.placement_strategy)


@dataclass
class FailureConfig:
    max_failures: int = 0
    fail_fast: bool = False


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(
        default_factory=CheckpointConfig)
    # experiment callbacks (ray_tpu.tune.callbacks.Callback): invoked by
    # the Tuner controller at trial lifecycle points
    callbacks: Optional[list] = None
    verbose: int = 1

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        return os.path.join(base, self.name or "experiment")
