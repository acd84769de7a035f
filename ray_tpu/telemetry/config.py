"""Telemetry env knobs — the single home for training-telemetry config.

Follows the ``attention_config()`` / ``ce_config()`` / ``comm_config()``
precedent: one frozen dataclass resolved from the environment once,
``refresh=True`` for tests and A/B drivers that flip flags after import.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Optional


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Training-telemetry knobs, resolved once from the environment.

    - ``RAY_TPU_TELEMETRY`` (default ``1``): step-level telemetry on the
      train steps returned by ``build_gpt_train``/``build_gpt_train_pp``
      and the bench drivers — per-step wall/sync timing (with an
      explicit ``block_until_ready``), compile-vs-steady split,
      tokens/sec, analytic-FLOPs MFU, HBM footprint from
      ``memory_analysis()`` and logical collective bytes/step.  ``0``
      turns the whole layer into a no-op (the wrapped step IS the raw
      step); the overhead budget when on is <1% of steady-state step
      time, enforced by ``tests/test_telemetry.py``.
    - ``RAY_TPU_PROFILE`` (default unset): a directory; when set, the
      step recorder captures a ``jax.profiler`` xplane trace of steps
      1..3 (the steady window right after compile) into it — a
      device timeline without editing the loop under test.
    """
    enabled: bool = True
    profile_dir: Optional[str] = None
    # steps captured by the xplane trace when profile_dir is set:
    # [profile_first, profile_first + profile_steps)
    profile_first: int = 1
    profile_steps: int = 3


_CONFIG: Optional[TelemetryConfig] = None


def telemetry_config(refresh: bool = False) -> TelemetryConfig:
    """The process-wide :class:`TelemetryConfig` (env read once, cached)."""
    global _CONFIG
    if _CONFIG is None or refresh:
        raw = os.environ.get("RAY_TPU_TELEMETRY", "1")
        if raw not in ("0", "1"):
            print(f"RAY_TPU_TELEMETRY={raw!r} unknown; using '1'",
                  file=sys.stderr)
            raw = "1"
        _CONFIG = TelemetryConfig(
            enabled=(raw == "1"),
            profile_dir=os.environ.get("RAY_TPU_PROFILE") or None,
        )
    return _CONFIG
