"""Per-layer metrics.  ``BENCHMARK.json`` says what a metric is (its
layer, unit, source, whether lower is better, the end-to-end metric it
should move, its cells) and nothing here repeats that; a file under
``benchmark/layer_metrics/`` says how it is read: ``what`` in words and
the ``reader``.  The file is ``<name>.json``, or, for a metric that the
manifest had to split by what it moves (``decode_step_ms.itl``,
``decode_step_ms.batch``), the name without its last dotted part: one
reader for the one quantity.  A reader that finds nothing to read
returns ``None`` and the metric is left out of the line.

Reader kinds a ``.json`` can name without any code:

- ``fact``: a number the runner collected (a counter's delta, a client
  timing), by ``key``;
- ``trace_ms``: device milliseconds of the operation families matching
  ``patterns`` (regular expressions over the jax operation name),
  divided by the executions of the executable ``per_module`` in the
  traced window, or by the harness's ticks if none is named;
- ``trace_module_ms``: device milliseconds per execution of the
  executables matching ``module``;
- ``trace_idle_share``: 100 x (1 - device busy / traced window);
- ``trace_exposed_collective_ms``: collective time with no compute
  running on that device, per tick;
- ``python``: ``<file>.py`` beside the ``.json`` defines ``read(ctx)``.

``ctx`` holds ``facts``, ``trace`` (the reduction of
``benchmark.reduce.trace.reduce_trace``, or None), ``config`` (the
configuration file), ``traffic`` and ``device_kind``.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Any, Dict, Optional

from benchmark.harness import common
from benchmark.reduce import trace as trace_mod

METRIC_DIR = os.path.join(common.BENCH_DIR, "layer_metrics")


def _module_calls(trace: Dict[str, Any], pattern: str):
    reg = re.compile(pattern)
    hit = [m for name, m in trace.get("modules", {}).items()
           if reg.search(name)]
    return (sum(m["calls"] for m in hit), sum(m["seconds"] for m in hit))


def _read(spec: Dict[str, Any], stem: str, ctx: Dict[str, Any]
          ) -> Optional[float]:
    reader = spec["reader"]
    kind = reader["kind"]
    name = os.path.basename(stem)
    trace = ctx.get("trace")
    if kind == "fact":
        return ctx["facts"].get(reader["key"])
    if kind == "python":
        mod_spec = importlib.util.spec_from_file_location(
            "layer_metric_" + re.sub(r"\W", "_", name), stem + ".py")
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        return module.read(ctx)
    if not trace:
        return None
    if kind == "trace_idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if kind == "trace_module_ms":
        calls, seconds = _module_calls(trace, reader["module"])
        return 1e3 * seconds / calls if calls else None
    if kind in ("trace_ms", "trace_exposed_collective_ms"):
        if reader.get("per_module"):
            per, _ = _module_calls(trace, reader["per_module"])
        else:
            per = trace["ticks"]
        if not per:
            return None
        if kind == "trace_exposed_collective_ms":
            if trace["n_devices"] < 2:
                return None
            return 1e3 * trace["collective_exposed_s"] / per
        seconds = trace_mod.seconds_matching(trace, reader["patterns"])
        return 1e3 * seconds / per if seconds else None
    raise ValueError(f"layer metric {name}: unknown reader kind {kind!r}")


def reader_file(name: str) -> str:
    """The reader of metric ``name``, without its extension."""
    for stem in (name, name.rpartition(".")[0]):
        if stem and os.path.exists(os.path.join(METRIC_DIR, stem + ".json")):
            return os.path.join(METRIC_DIR, stem)
    raise FileNotFoundError(f"layer metric {name}: no reader under "
                            f"{METRIC_DIR}")


def read_layer_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    stem = reader_file(name)
    value = _read(common.load_json(stem + ".json"), stem, ctx)
    return None if value is None else float(value)
