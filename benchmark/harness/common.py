"""What every part of the harness shares: where things are, how a cell's
files are found by the names in ``BENCHMARK.json``, and the device block
of a result line."""

from __future__ import annotations

import json
import os
import statistics
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(CHECKOUT, ".bench_out")      # traces; git-ignored


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def load_traffic(name: str) -> Dict[str, Any]:
    """A mix by name.  ``extends`` names another mix whose parameters it
    takes, with its own laid over them: the same mix under another name
    (a configuration and a mix pair once) is then not a copy."""
    mix = load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))
    base = mix.pop("extends", None)
    return dict(load_traffic(base), **mix) if base else mix


def cell_files(name: str, rehearsal: bool = False) -> Dict[str, Any]:
    """A cell is an entry of ``workloads``.  Its configuration and mix are
    the files those names point at; what belongs to the pair, the train
    recipe or the engine's sizing, is ``cells/<cell>.json``.  A rehearsal
    on the CPU swaps in the toy configuration and its sizing."""
    man = manifest()
    cell = next((w for w in man["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: "
                         f"{[w['name'] for w in man['workloads']]})")
    conf = next(c for c in man["configs"] if c["name"] == cell["config"])
    if rehearsal:
        config = load_json(os.path.join(BENCH_DIR, "configs",
                                        "toy.rehearsal.json"))
        sizing = config["sizing"]
    else:
        config = load_json(os.path.join(CHECKOUT, conf["file"]))
        sizing = load_json(os.path.join(BENCH_DIR, "cells", name + ".json"))
    return {
        "cell": cell,
        "config": config,
        "sizing": sizing,
        "traffic": load_traffic(cell["traffic"]),
        "end_to_end": [m for m in man["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in man["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def use_compile_cache() -> str:
    """jax's persistent compile cache for this process and every worker
    it starts: where ``JAX_COMPILATION_CACHE_DIR`` says if set, else the
    fixed ``<checkout>/.jax_cache`` (the program's own rule,
    ``ray_tpu/_private/compile_cache.py``; the variable is exported so
    that workers, which inherit the environment, agree)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def device_block(devices) -> Dict[str, Any]:
    """The device as jax reports it in the process that holds it.  The
    peak is the fullest chip's ``peak_bytes_in_use`` plus
    ``peak_bytes_reserved``: on this runtime an executable's temporaries
    are reserved, not counted as in use (0.92 GB in use beside 10.26 GB
    reserved after a 24 x 1024 train step)."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0))
                     + int(s.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks)}


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: List[float]) -> float:
    return statistics.median(values)
