"""``ray_tpu.inference`` — TPU-native continuous-batching inference.

The serving-side counterpart of ``ray_tpu.models.training``: a paged KV
cache (:mod:`~ray_tpu.inference.kv_cache`), bucketed AOT-compiled
prefill + fixed-slot decode steps (:mod:`~ray_tpu.inference.engine`),
a host-side continuous-batching scheduler
(:mod:`~ray_tpu.inference.scheduler`), per-sequence-PRNG sampling
(:mod:`~ray_tpu.inference.sampling`) and a ``serve`` deployment that
streams tokens through ``handle_request_streaming``
(:mod:`~ray_tpu.inference.serve_gpt`).  Config via ``RAY_TPU_INFER_*``
(:func:`infer_config`).
"""

from ray_tpu.inference.config import (InferConfig,  # noqa: F401
                                      infer_config, default_buckets)
from ray_tpu.inference.engine import (InferenceEngine,  # noqa: F401
                                      StepEvent)
from ray_tpu.inference.kv_cache import (HandoffContentMissing,  # noqa: F401
                                        KVCache, KVHandoff,
                                        PageAllocator, PrefixIndex)
from ray_tpu.inference.sampling import SamplingParams  # noqa: F401
from ray_tpu.inference.scheduler import (DeadlineExceededError,  # noqa: F401
                                         QueueFullError,
                                         Request, SlotScheduler)

__all__ = [
    "InferConfig", "infer_config", "default_buckets",
    "InferenceEngine", "StepEvent", "KVCache", "PageAllocator",
    "PrefixIndex", "KVHandoff", "HandoffContentMissing",
    "SamplingParams", "QueueFullError", "DeadlineExceededError",
    "Request", "SlotScheduler",
]
