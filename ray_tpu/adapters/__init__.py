"""Multi-tenant adapter serving (r25): LoRA factors as call args.

"Millions of users" is not one model — it is one base model plus
thousands of per-tenant low-rank adapters.  This package is the
multi-tenant seam across the stack:

- :mod:`~ray_tpu.adapters.lora` — the math: A/B factor initialization,
  merged-weights construction (the parity oracle), and the device-side
  **adapter bank** (``[N, L, in, r]`` stacked factors, slot 0 the
  all-zeros identity) that rides every AOT inference executable as a
  call argument — the r14 ``set_params`` lesson applied to tenants:
  hot-swap must be recompile-free, so adapters are *data*, never
  constants.
- :mod:`~ray_tpu.adapters.store` — :class:`AdapterStore`, the
  fleet-shared content-addressed publication point (object-store
  backed like ``WeightStore``), keyed
  ``(model_id, version)`` with a monotonic per-model latest pointer.
- :mod:`~ray_tpu.adapters.registry` — :class:`AdapterRegistry`, the
  per-engine resident-adapter bookkeeping: which ``(model_id,
  version)`` sits in which bank slot, LRU over unpinned residents,
  pins from in-flight requests so factors mid-decode can never be
  evicted or rewritten under the requests using them (a republish
  lands in a fresh row until the old version's pins drain).
- :mod:`~ray_tpu.adapters.config` — :class:`LoraConfig` and the
  ``RAY_TPU_LORA_*`` / ``RAY_TPU_ADAPTER_CACHE`` env knobs.

The engine applies per-slot adapters inside the batched decode step
via a grouped matmul (gather factors by slot id, two skinny einsums),
so co-batched tenants share one tick; requests without a ``model_id``
ride bank slot 0 and are bit-identical to an adapter-free engine.
"""

from ray_tpu.adapters.config import LoraConfig, lora_config
from ray_tpu.adapters.lora import (adapter_nbytes, bank_install,
                                   bank_zeros, init_adapter,
                                   merge_adapter, salt_bytes,
                                   target_dims)
from ray_tpu.adapters.registry import AdapterRegistry
from ray_tpu.adapters.store import AdapterStore, AdapterUnavailableError

__all__ = [
    "LoraConfig", "lora_config", "target_dims", "init_adapter",
    "merge_adapter", "bank_zeros", "bank_install", "adapter_nbytes",
    "salt_bytes", "AdapterStore", "AdapterUnavailableError",
    "AdapterRegistry",
]
