"""TPU/device profiling helpers.

Parity: the reference's profiling story (``ray timeline`` +
``torch.profiler`` integration in train); TPU-native: wraps
``jax.profiler`` so a train loop (or a Serve replica) captures an
xplane trace viewable in TensorBoard/XProf or Perfetto alongside the
cluster-level chrome trace (``ray-tpu timeline``).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator, Optional

from ray_tpu.util import tracing


@contextlib.contextmanager
def profile_device(logdir: Optional[str] = None,
                   host_tracer_level: int = 2) -> Iterator[str]:
    """Capture a jax device profile around a code block.

    Yields the log directory; afterwards it holds
    ``plugins/profile/<ts>/*.xplane.pb`` (TensorBoard "Profile" tab or
    ``xprof``) and a ``*.trace.json.gz`` for Perfetto.
    """
    import jax
    logdir = logdir or os.path.join(
        "/tmp", f"ray_tpu_profile_{int(time.time())}")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = host_tracer_level
    with jax.profiler.trace(logdir, profiler_options=opts):
        yield logdir


# A named region inside a device profile: the program's one span
# primitive, which annotates the profiler's trace wherever jax is loaded.
annotate = tracing.span


def device_memory_stats() -> dict:
    """Per-device live-memory stats (HBM pressure at a glance)."""
    import jax
    out = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — backend may not support it
            stats = {}
        out[str(d)] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        }
    return out
