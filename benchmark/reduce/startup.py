"""The set-up's parts, read from the program's own start-up timeline.

``setup_s`` is spent before the window and across three processes (the
driver, its in-process node manager, the worker that owns the chips), so
no device trace and no window delta sees it.  The program keeps the
record itself (``ray_tpu/util/tracing.py``): every process appends its
``setup/*`` spans, its ``infer/compile`` spans and jax's own
``jax/trace`` / ``jax/lower`` / ``jax/load`` / ``jax/compile`` time
spans, one JSON line a record, to
``<session_dir>/logs/startup_<pid>.jsonl``, and
``ray_tpu.util.state.startup_timeline()`` merges a session's files,
sorted by ``start`` (epoch seconds), each record with its ``pid`` and
``role``.  ``run.py`` reads the metrics after ``ray_tpu.shutdown()``, in
the process that called ``init``: the files outlive the session, and the
program remembers where they lie.

**Names this depends on**, so that a refactor of the boot path keeps them
or changes the benchmark first: ``setup/init`` (``ray_tpu.init()``,
whole; its ``start`` is the timeline's zero); ``setup/actor_init`` and
``setup/task`` with the attribute ``chips`` (how many chips the node
manager gave that process: the first such record with ``chips`` > 0 names
the worker that owns the cell's chips, and starts as it takes them);
``setup/worker_boot`` with ``exec_epoch`` (the process's start, from
``/proc/self/stat``); ``setup/weights``, ``setup/engine``,
``setup/first_step``, ``infer/compile``; ``jax/trace``, ``jax/lower``,
``jax/load``, ``jax/compile`` with ``fun_name``.  And on two facts of
the runner: ``setup_s`` (the cut: nothing after
``setup/init``'s start + ``setup_s`` is read, the window had opened by
then) and ``worker_ready_s`` (the runner's own stamps around ``init``
and the worker's first ``jax.devices()``).

Every reader returns ``None``, and never raises, where the API, a record
or a fact is missing: the parent of the PR that brought the record, a
rehearsal without a chip worker.  The metric is then left out of the
line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

# metric -> the chip worker's records it sums up to the cut
_SUMS = {"setup_trace_s": "jax/trace", "setup_lower_s": "jax/lower",
         "setup_load_s": "jax/load", "setup_compile_s": "jax/compile",
         "setup_engine_compile_s": "infer/compile"}
# metric -> the chip worker's one record whose duration it is (the first)
_SPANS = {"setup_weights_s": "setup/weights",
          "setup_first_step_s": "setup/first_step",
          "setup_engine_build_s": "setup/engine"}

_TIMELINE: Optional[List[Dict[str, Any]]] = None


def timeline() -> Optional[List[Dict[str, Any]]]:
    """This process's session's records, read once; ``None`` where the
    program keeps none (or ``init`` never ran here)."""
    global _TIMELINE
    if _TIMELINE is None:
        try:
            from ray_tpu.util import state
            _TIMELINE = state.startup_timeline() or None
        except Exception:       # noqa: BLE001 — a reader never raises
            return None
    return _TIMELINE


def _unnested(records: List[Dict[str, Any]]) -> float:
    """Seconds the records cover, a thread at a time: a function traced
    inside another's trace has a record inside that one's."""
    total, end, tid = 0.0, None, None
    for r in sorted(records, key=lambda r: (r.get("tid"), r["start"])):
        if r.get("tid") != tid:
            tid, end = r.get("tid"), None
        if end is None or r["start"] >= end:
            total += r["dur"]
            end = r["start"] + r["dur"]
    return total


def parts(records: Optional[List[Dict[str, Any]]],
          facts: Dict[str, Any]) -> Dict[str, float]:
    """Every set-up metric that ``records`` and ``facts`` can give,
    ``{metric: value}``; one that they cannot is absent."""
    out: Dict[str, float] = {}
    init = next((r for r in records or ()
                 if r["name"] == "setup/init"), None)
    if init is None:
        return out
    t0 = init["start"]
    out["setup_runtime_up_s"] = init["dur"]
    cut = t0 + facts["setup_s"] if "setup_s" in facts else None
    took = next((r for r in records
                 if r["name"] in ("setup/actor_init", "setup/task")
                 and r["start"] >= t0
                 and (r.get("attributes") or {}).get("chips", 0) > 0), None)
    if took is None or cut is None:
        return out
    out["setup_worker_place_s"] = took["start"] - (t0 + init["dur"])
    if "worker_ready_s" in facts:
        out["setup_to_devices_s"] = (t0 + facts["worker_ready_s"]
                                     - took["start"])
    mine = [r for r in records
            if r.get("pid") == took["pid"] and r["start"] < cut]
    boot = next((r for r in mine if r["name"] == "setup/worker_boot"), None)
    exec_epoch = ((boot or {}).get("attributes") or {}).get("exec_epoch")
    if exec_epoch is not None:
        out["setup_worker_boot_s"] = (boot["start"] + boot["dur"]
                                      - exec_epoch)
    for metric, name in _SUMS.items():
        hit = [r for r in mine if r["name"] == name]
        # jax's parts read 0.0 where there is no record (a warm run
        # compiles nothing), the engine's nothing (a train cell has none)
        if hit or name.startswith("jax/"):
            out[metric] = (_unnested(hit) if name == "jax/trace"
                           else sum(r["dur"] for r in hit))
    out["setup_executables"] = float(sum(
        r["name"] in ("jax/load", "jax/compile") for r in mine))
    for metric, name in _SPANS.items():
        hit = next((r for r in mine if r["name"] == name), None)
        if hit is not None:
            out[metric] = hit["dur"]
    return out


def read_metric(name: str, ctx: Dict[str, Any],
                records: Optional[List[Dict[str, Any]]] = None
                ) -> Optional[float]:
    """Metric ``name`` from ``records`` (absent: this process's
    session's) and ``ctx["facts"]``; ``None`` where it cannot be read."""
    try:
        return parts(timeline() if records is None else records,
                     ctx.get("facts") or {}).get(name)
    except Exception:           # noqa: BLE001 — a reader never raises
        return None
