"""Chrome-trace / Perfetto exporter: one unified host+train timeline.

Merges three in-process sources into one ``traceEvents`` JSON that
loads in Perfetto / ``chrome://tracing``:

- the host-side span recorder (``ray_tpu.util.tracing`` fallback
  recorder — submit/task spans plus the named train-loop scopes the
  telemetry wrapper emits when tracing is enabled, and, always, the
  process's start-up record: ``setup/*`` spans and jax's own
  ``jax/trace`` / ``jax/lower`` / ``jax/load`` / ``jax/compile`` time
  spans, a step's compile among them),
- every live :class:`~ray_tpu.telemetry.step.StepTelemetry` recorder's
  per-step records (step / dispatch / sync complete-events),
- the r24 per-request flight recorder
  (:mod:`ray_tpu.telemetry.trace` — routing, handoff, prefill and
  decode spans, grouped by replica).

The dashboard ``/api/timeline`` appends the same events to the
task-event trace, so a browser pointed at the head node sees train
steps on the cluster timeline; ``export(path)`` writes the standalone
JSON object form (``{"traceEvents": [...]}``) the on-chip drivers
attach next to their xplane captures.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional


def _span_events(spans) -> List[Dict[str, Any]]:
    """util.tracing fallback-recorder spans -> Chrome complete events."""
    evs = []
    for s in spans:
        start = s.get("start")
        if start is None:
            continue
        # durations come from the monotonic clock when the recorder has
        # one (see util/tracing.py); "end" is epoch-placed either way
        dur = s.get("dur")
        if dur is None:
            end = s.get("end")
            if end is None:
                continue
            dur = max(end - start, 0.0)
        evs.append({
            "name": s.get("name", "?"), "cat": "host", "ph": "X",
            "ts": start * 1e6, "dur": dur * 1e6,
            "pid": "host", "tid": str(s.get("tid", "main")),
            "args": dict(s.get("attributes") or {}),
        })
    return evs


def trace_events(include_host: bool = True,
                 include_steps: bool = True,
                 include_requests: bool = True) -> List[Dict[str, Any]]:
    """Every exportable event currently held in this process."""
    evs: List[Dict[str, Any]] = []
    if include_host:
        from ray_tpu.util import tracing
        evs.extend(_span_events(tracing.recorded_spans()))
    if include_steps:
        from ray_tpu.telemetry.step import recorders
        for rec in recorders():
            evs.extend(rec.chrome_events())
    if include_requests:
        # r24 per-request spans: the flight-recorder ring joins the
        # same timeline, so /api/timeline shows serving requests next
        # to train steps for free
        from ray_tpu.telemetry import trace
        evs.extend(trace.chrome_events())
    evs.sort(key=lambda e: e.get("ts", 0))
    return evs


def export(path: Optional[str] = None, *,
           extra_events: Optional[List[Dict[str, Any]]] = None) -> str:
    """Perfetto JSON-object trace of everything recorded so far."""
    evs = trace_events()
    if extra_events:
        evs = sorted(evs + list(extra_events),
                     key=lambda e: e.get("ts", 0))
    out = json.dumps({"traceEvents": evs, "displayTimeUnit": "ms"})
    if path:
        with open(path, "w") as f:
            f.write(out)
    return out
