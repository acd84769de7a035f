"""State API (parity: ``python/ray/util/state``): programmatic listing of
cluster entities, backed by the control plane tables.

Every ``list_*`` takes ``filters`` — ``(key, op, value)`` triples with
the reference's predicate set (``= != < <= > >= contains in``,
``util/state/common.py`` role) — and ``offset`` for pagination; rows
come back in stable order so ``offset``/``limit`` windows stitch.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.worker import global_worker

Filter = Tuple[str, str, Any]


def _cp():
    return global_worker().cp


def _match(row: Dict[str, Any], key: str, op: str, value: Any) -> bool:
    have = row.get(key)
    if op in ("=", "=="):
        return str(have) == str(value)
    if op == "!=":
        return str(have) != str(value)
    if op == "contains":
        return str(value) in str(have)
    if op == "in":
        if isinstance(value, (str, bytes)):
            # a bare string would be iterated per-character and match
            # nothing, silently — make the misuse loud
            raise TypeError(
                "'in' filter value must be a list/tuple/set of "
                f"candidates, got {type(value).__name__}")
        return str(have) in [str(v) for v in value]
    # ordered comparisons: numeric when both sides parse, else lexical
    try:
        a, b = float(have), float(value)  # type: ignore[arg-type]
    except (TypeError, ValueError):
        a, b = str(have), str(value)      # type: ignore[assignment]
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    raise ValueError(f"unsupported filter op {op!r}")


def _window(rows: List[Dict[str, Any]],
            filters: Optional[List[Filter]], limit: int,
            offset: int) -> List[Dict[str, Any]]:
    if filters:
        for key, op, value in filters:
            rows = [r for r in rows if _match(r, key, op, value)]
    return rows[offset:offset + limit]


def list_nodes(limit: int = 1000, filters: Optional[List[Filter]] = None,
               offset: int = 0) -> List[Dict[str, Any]]:
    out = []
    for info in _cp().list_nodes():
        out.append({
            "node_id": info["node_id"].hex(),
            "state": info["state"],
            "ip": info.get("ip"),
            "resources_total": info.get("resources_total", {}),
            "resources_available": info.get("resources_available", {}),
            "labels": info.get("labels", {}),
            "load": info.get("load", {}),
            "death_reason": info.get("death_reason", ""),
        })
    out.sort(key=lambda r: r["node_id"])
    return _window(out, filters, limit, offset)


def list_actors(limit: int = 1000, filters: Optional[List[Filter]] = None,
                offset: int = 0) -> List[Dict[str, Any]]:
    out = []
    for info in _cp().list_actors():
        row = {
            "actor_id": info["actor_id"].hex(),
            "class_name": info.get("class_name"),
            "state": info.get("state"),
            "name": info.get("name"),
            "pid": info.get("pid"),
            "node_id": (info.get("node_id").hex()
                        if info.get("node_id") else None),
            "num_restarts": info.get("num_restarts", 0),
        }
        out.append(row)
    out.sort(key=lambda r: r["actor_id"])
    return _window(out, filters, limit, offset)


def list_tasks(limit: int = 10000,
               filters: Optional[List[Filter]] = None,
               offset: int = 0) -> List[Dict[str, Any]]:
    events = _cp().list_task_events(limit=100000)
    latest: Dict[str, Dict[str, Any]] = {}
    for ev in events:
        tid = ev.get("task_id")
        cur = latest.setdefault(tid, {"task_id": tid})
        cur["state"] = ev.get("state")
        if ev.get("name"):
            cur["name"] = ev["name"]
        if ev.get("node"):
            cur["node_id"] = ev["node"]
        cur.setdefault("events", []).append(
            {"state": ev.get("state"), "time": ev.get("time")})
    rows = sorted(latest.values(), key=lambda r: r["task_id"] or "")
    return _window(rows, filters, limit, offset)


def list_objects(limit: int = 10000,
                 filters: Optional[List[Filter]] = None,
                 offset: int = 0) -> List[Dict[str, Any]]:
    rows = _cp().list_objects()
    rows.sort(key=lambda r: str(r.get("object_id", "")))
    return _window(rows, filters, limit, offset)


def list_placement_groups(limit: int = 1000,
                          filters: Optional[List[Filter]] = None,
                          offset: int = 0) -> List[Dict[str, Any]]:
    out = []
    for info in _cp().list_placement_groups():
        out.append({
            "placement_group_id": info["pg_id"].hex(),
            "name": info.get("name", ""),
            "state": info.get("state"),
            "strategy": info.get("strategy"),
            "bundles": info.get("bundles", []),
        })
    out.sort(key=lambda r: r["placement_group_id"])
    return _window(out, filters, limit, offset)


def summarize_tasks() -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for task in list_tasks():
        counts[task.get("state", "?")] = counts.get(
            task.get("state", "?"), 0) + 1
    return counts


def summarize_actors() -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for actor in list_actors():
        counts[actor.get("state", "?")] = counts.get(
            actor.get("state", "?"), 0) + 1
    return counts


def summarize_objects() -> Dict[str, Any]:
    return _cp().objects_summary()


def startup_timeline(session_dir: Optional[str] = None
                     ) -> List[Dict[str, Any]]:
    """How a session's processes came up: the start-up records
    (``setup/*`` and ``infer/compile`` spans, jax's ``jax/trace`` /
    ``jax/lower`` / ``jax/load`` / ``jax/compile`` time spans:
    ``util/tracing.py``) of every process that wrote
    ``<session_dir>/logs/startup_<pid>.jsonl``, sorted by ``start``
    (epoch seconds), each with the ``pid`` and the ``role`` of its
    process (``driver``, ``worker``, ``node``).

    ``session_dir`` absent: this process's session, live or, in the
    process that called ``init``, after ``shutdown()`` (the files
    outlive it).  Reads files only, so it asks nothing of a cluster."""
    import glob
    import json
    import os

    from ray_tpu.util import tracing
    session_dir = session_dir or tracing.session_dir()
    if session_dir is None:
        raise RuntimeError("no session directory: ray_tpu.init() has "
                           "not run in this process, and none was given")
    out: List[Dict[str, Any]] = []
    for path in glob.glob(os.path.join(session_dir, "logs",
                                       "startup_*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:      # a line still being written
                    continue
    out.sort(key=lambda r: r["start"])
    return out
