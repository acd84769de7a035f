"""From a profiler trace to numbers: device busy and idle time, time per
family of device operations, exposed collective time, and the idle gaps
named by what the host was doing in them.

The measured window is marked by the benchmark itself: every unit of
work (a train step, an engine tick) runs inside a
``jax.profiler.TraceAnnotation(TICK)`` written by the harness, on the
profiler's clock.  The window runs from the first tick's start to the
last tick's end; per-step and per-tick metrics divide by the number of
ticks in it.
"""

from __future__ import annotations

import collections
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark.reduce.xplane import Event, Line, Plane, read_xplane

TICK = "bench/tick"
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
_NAME_OK = re.compile(r"[^A-Za-z0-9_.\-/]")
MIN_GAP_PS = 10_000_000          # 10 us: shorter gaps are launch latency


def clean_name(name: str, limit: int = 64) -> str:
    """A family name in the characters a metric name may have."""
    return _NAME_OK.sub("_", name.strip().rstrip(":"))[:limit]


def family(ev: Event) -> str:
    """The jax name of a device operation (``jit(step)/jvp(gpt/attn)/
    attn/pack2/pallas_call``) where the profiler has it, else the HLO
    instruction's name without its number."""
    op = ev.stats.get("tf_op")
    if op:
        return str(op).rstrip(":")
    return re.sub(r"\.\d+$", "", ev.display.lstrip("%"))


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(ev: Event, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    a, b = max(ev.start_ps, lo), min(ev.end_ps, hi)
    return (a, b) if b > a else None


def _innermost_at(events: List[Event], times: List[int]) -> List[Optional[Event]]:
    """For each time (ascending), the shortest event of one thread's
    properly nested events that contains it."""
    order = sorted(events, key=lambda e: (e.start_ps, -e.dur_ps))
    out: List[Optional[Event]] = []
    stack: List[Event] = []
    i = 0
    for t in times:
        while i < len(order) and order[i].start_ps <= t:
            while stack and stack[-1].end_ps < order[i].start_ps:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end_ps < t:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def _host_threads(planes: List[Plane]) -> List[List[Event]]:
    return [ln.events for p in planes if p.name.startswith("/host:")
            for ln in p.lines if ln.events]


def _name_gaps(gaps: List[Tuple[int, int]], threads: List[List[Event]],
               tick_thread: Optional[int]) -> Dict[str, int]:
    """Idle picoseconds by what the host was doing at the middle of each
    gap: the innermost span of the thread that runs the ticks, else the
    innermost span of any other thread."""
    if not gaps:
        return {}
    gaps = sorted(gaps)
    mids = [(a + b) // 2 for a, b in gaps]
    per_thread = [_innermost_at(evs, mids) for evs in threads]
    named: Dict[str, int] = collections.defaultdict(int)
    for g, (a, b) in enumerate(gaps):
        pick = None
        if tick_thread is not None:
            pick = per_thread[tick_thread][g]
        if pick is None:
            # between ticks: what any thread was in, python frames
            # (``$file:line name``) before runtime threads, shortest first
            others = [t[g] for t in per_thread if t[g] is not None]
            others.sort(key=lambda e: (not e.name.startswith("$"),
                                       e.dur_ps))
            pick = others[0] if others else None
        named[clean_name(pick.name.lstrip("$")) if pick is not None
              else "nothing_recorded"] += b - a
    return named


def reduce_trace(path: str) -> Dict[str, Any]:
    """The reduction every trace-sourced metric reads.  Seconds are per
    device, averaged over the devices in the trace."""
    planes = read_xplane(
        path, want_lines=lambda n: n.startswith(("/device:TPU:", "/host:")))
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and p.line("XLA Ops") is not None
               and p.line("XLA Ops").events]
    if not devices:
        return {}
    threads = _host_threads(planes)
    ticks, tick_thread = [], None
    for k, evs in enumerate(threads):
        mine = [e for e in evs if e.name == TICK]
        if len(mine) > len(ticks):
            ticks, tick_thread = mine, k
    if ticks:
        lo = min(e.start_ps for e in ticks)
        hi = max(e.end_ps for e in ticks)
    else:
        ops = [e for p in devices for ln in p.lines for e in ln.events]
        lo = min(e.start_ps for e in ops)
        hi = max(e.end_ps for e in ops)
    n_dev = len(devices)
    op_ps: Dict[str, int] = collections.defaultdict(int)
    busy_ps = coll_ps = exposed_ps = 0
    mod_ps: Dict[str, int] = collections.defaultdict(int)
    mod_calls: Dict[str, int] = collections.defaultdict(int)
    gap_ps: Dict[str, int] = collections.defaultdict(int)
    for p in devices:
        every, compute, coll = [], [], []
        for ev in (p.line("XLA Modules") or Line("", [])).events:
            if lo <= ev.start_ps < hi:
                # ``jit_decode(1234...)``: the executable, without its id
                name = re.sub(r"\(\d+\)$", "", ev.display)
                mod_ps[name] += ev.dur_ps
                mod_calls[name] += 1
        for ln in p.lines:
            for ev in ln.events:
                span = _clip(ev, lo, hi)
                if span is None:
                    continue
                is_coll = bool(_COLLECTIVE.search(ev.display)
                               or _COLLECTIVE.search(ev.name[:200]))
                if is_coll:
                    coll.append(span)
                if ln.name != "XLA Ops":
                    continue
                every.append(span)
                if _CONTAINER.match(ev.display.lstrip("%")):
                    continue
                name = family(ev)
                op_ps[name] += span[1] - span[0]
                if not is_coll:
                    compute.append(span)
        busy = union(every)
        busy_ps += total(busy)
        coll_u = union(coll)
        coll_ps += total(coll_u)
        exposed_ps += total(subtract(coll_u, union(compute)))
        gaps = [g for g in subtract([(lo, hi)], busy)
                if g[1] - g[0] >= MIN_GAP_PS]
        for name, ps in _name_gaps(gaps, threads, tick_thread).items():
            gap_ps[name] += ps
    stats = devices[0].stats
    return {
        "window_s": (hi - lo) / 1e12,
        "busy_s": busy_ps / n_dev / 1e12,
        "n_devices": n_dev,
        "ticks": len(ticks),
        "device_type": stats.get("device_type_string"),
        "op_seconds": {k: v / n_dev / 1e12 for k, v in op_ps.items()},
        "modules": {k: {"calls": mod_calls[k] / n_dev,
                        "seconds": v / n_dev / 1e12}
                    for k, v in mod_ps.items()},
        "collective_s": coll_ps / n_dev / 1e12,
        "collective_exposed_s": exposed_ps / n_dev / 1e12,
        "idle_gap_seconds": {k: v / n_dev / 1e12
                             for k, v in gap_ps.items()},
    }


def breakdown(reduced: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """The ``breakdown`` of a traced run's result line."""
    def rank(d):
        return [[clean_name(k), v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(reduced.get("op_seconds", {})),
            "idle_gaps": rank(reduced.get("idle_gap_seconds", {}))}


def seconds_matching(reduced: Dict[str, Any], patterns: List[str]) -> float:
    """Device seconds of the operation families any pattern matches."""
    regs = [re.compile(p) for p in patterns]
    return sum(v for k, v in reduced.get("op_seconds", {}).items()
               if any(r.search(k) for r in regs))
