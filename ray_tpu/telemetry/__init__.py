"""Step-level training telemetry.

The tracing/metrics/dashboard plumbing only ever saw Ray-parity
tasks.  This package instruments the train step itself:

- :class:`StepTelemetry` / :func:`instrument` wrap a jitted step and
  emit per-step records (wall/dispatch/sync with a blocking sync,
  compile-vs-steady split, tokens/sec, analytic-FLOPs MFU, HBM from
  ``memory_analysis()``, logical collective bytes/step),
- :mod:`~ray_tpu.telemetry.chrome_trace` exports a unified host+train
  Perfetto timeline (also merged into the dashboard ``/api/timeline``),
- per-step Prometheus series (``train_step_seconds``, ``train_mfu``,
  ``train_collective_bytes``) flow through the control-plane metrics
  to ``/metrics``,
- :meth:`StepTelemetry.summary` is the block a driver reports
  (``benchmark/run.py`` reads ``train_step_ms`` from the records).

``RAY_TPU_TELEMETRY=0`` disables everything (identity wrapper);
``RAY_TPU_PROFILE=<dir>`` adds an xplane capture of the first steady
steps.  See :func:`telemetry_config`.
"""

from ray_tpu.telemetry import chrome_trace  # noqa: F401
from ray_tpu.telemetry.ckpt import CkptTelemetry  # noqa: F401
from ray_tpu.telemetry.data import DataTelemetry  # noqa: F401
from ray_tpu.telemetry.config import (TelemetryConfig,  # noqa: F401
                                      telemetry_config)
from ray_tpu.telemetry.elastic import ElasticTelemetry  # noqa: F401
from ray_tpu.telemetry.fleet import FleetTelemetry  # noqa: F401
from ray_tpu.telemetry.flops import (chip_peak_tflops,  # noqa: F401
                                     gpt_fwd_flops_per_token,
                                     gpt_train_flops_per_token, mfu)
from ray_tpu.telemetry.infer import InferTelemetry  # noqa: F401
from ray_tpu.telemetry.rl import RLTelemetry  # noqa: F401
from ray_tpu.telemetry.step import (StepTelemetry,  # noqa: F401
                                    instrument, recorders)

__all__ = [
    "TelemetryConfig", "telemetry_config",
    "StepTelemetry", "instrument", "recorders",
    "InferTelemetry",
    "RLTelemetry",
    "CkptTelemetry",
    "DataTelemetry",
    "ElasticTelemetry",
    "FleetTelemetry",
    "chrome_trace",
    "chip_peak_tflops", "gpt_fwd_flops_per_token",
    "gpt_train_flops_per_token", "mfu",
]
