"""The program's own spans in a profiler trace, and the device's idle
time put down to the span the host was in.

The program opens its spans through ``ray_tpu.util.tracing.span``, which
is a ``TraceAnnotation`` on the profiler's clock: ``infer/step`` with
``infer/admit``, ``infer/prefill*``, ``infer/decode``, ``infer/verify``,
``infer/sample``, ``infer/deliver`` and ``infer/compile`` inside an
engine tick, ``serve/fanout`` in the serve pump, and ``<label>/dispatch``,
``/sync``, ``/loss_read``, ``/record`` inside the trainer's step
annotation ``<label>``.  ``reduce/trace.py`` names an idle gap by the
Python frame at its middle; here every idle picosecond of the same
window goes to the innermost *program* span that covers it (frames are
ignored), so the pieces add up to that reduction's idle time.

How a reader uses it (``layer_metrics/idle_sync_ms_per_step.py`` is the
whole pattern)::

    from benchmark.reduce import spans
    def read(ctx):
        return spans.read_metric("idle_sync_ms_per_step")

``read_metric`` finds the run's trace itself (``ctx`` carries no path:
the newest ``.xplane.pb`` under ``.bench_out/trace/``, whose cell
directory ``run.py`` empties before each run), parses it once per
process, and returns ``None``, never raising, when the trace, the
device planes or the program's spans are not there (a CPU trace, a
program from before the spans).  ``python3 -m benchmark.reduce.spans
<file>`` prints everything it reads from one trace.

Only what is needed is kept of a trace: of the host planes the events
with a program span's name or the harness's ``bench/tick``, of each
device plane the merged busy intervals of its ``XLA Ops`` line.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.reduce.trace import TICK, subtract, total, union
from benchmark.reduce.xplane import (_fields, _map_entry, _signed, _stat)

Interval = Tuple[int, int]

TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out", "trace")
_ENGINE = re.compile(r"^(infer|serve)/[a-z_]+$")
_TRAIN_PHASE = re.compile(r"^([^$/ ]+)/(dispatch|sync|loss_read|record)$")

# innermost span -> the phase its idle time is booked under; idle under
# no program span is the last phase of each map
SERVE_PHASES = {"infer/admit": "admit", "infer/prefill": "dispatch",
                "infer/prefill_cached": "dispatch",
                "infer/decode": "dispatch", "infer/verify": "dispatch",
                "infer/compile": "dispatch", "infer/sample": "fetch",
                "infer/deliver": "deliver", "infer/step": "deliver",
                None: "between_ticks"}
TRAIN_PHASES = {"sync": "sync", "dispatch": "step_host",
                "loss_read": "step_host", "record": "step_host",
                "step": "step_host", None: "outside_step"}
# metric (without the suffix the manifest splits it by) -> (phases, phase)
IDLE_READERS = {
    "idle_admit_ms_per_tick": (SERVE_PHASES, "admit"),
    "idle_dispatch_ms_per_tick": (SERVE_PHASES, "dispatch"),
    "idle_fetch_ms_per_tick": (SERVE_PHASES, "fetch"),
    "idle_deliver_ms_per_tick": (SERVE_PHASES, "deliver"),
    "idle_between_ticks_ms_per_tick": (SERVE_PHASES, "between_ticks"),
    "idle_sync_ms_per_step": (TRAIN_PHASES, "sync"),
    "idle_step_host_ms_per_step": (TRAIN_PHASES, "step_host"),
    "idle_outside_step_ms_per_step": (TRAIN_PHASES, "outside_step"),
}


@dataclasses.dataclass
class Span:
    name: str
    start_ps: int
    end_ps: int
    thread: str
    stats: Dict[str, object]

    @property
    def dur_ps(self) -> int:
        return self.end_ps - self.start_ps


@dataclasses.dataclass
class Trace:
    spans: List[Span]                       # program spans, by start
    ticks: List[Span]                       # bench/tick, every thread
    device_busy: List[List[Interval]]       # merged, one list a device
    train_label: Optional[str]
    extent: Optional[Interval] = None       # first to last host event

    def named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]

    def window(self) -> Optional[Tuple[int, int, int]]:
        """(start, end, ticks).  Start and end as ``reduce/trace.py``
        takes them, from the ticks of the one thread that holds most of
        them, so that idle time here is idle time there; the ticks
        counted are those of *every* thread inside that window (the
        serve pump's executor moves ``engine.step`` between threads,
        and that reduction's own ``ticks`` then counts one thread's)."""
        best: List[Span] = []
        by_thread: Dict[str, List[Span]] = {}
        for t in self.ticks:
            by_thread.setdefault(t.thread, []).append(t)
        for mine in by_thread.values():     # file order, as there
            if len(mine) > len(best):
                best = mine
        if not best:
            return None
        lo = min(t.start_ps for t in best)
        hi = max(t.end_ps for t in best)
        return lo, hi, sum(1 for t in self.ticks
                           if lo <= t.start_ps and t.end_ps <= hi)


# ------------------------------------------------------------------ parse
def _line_events(raw_line):
    name, t0_ns, raw_events = "", 0, []
    for f, _w, v in _fields(raw_line):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            t0_ns = _signed(v)
        elif f == 4:
            raw_events.append(v)
    return name, t0_ns * 1000, raw_events


def _parse(path: str) -> Trace:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    spans: List[Span] = []
    ticks: List[Span] = []
    busy: List[List[Interval]] = []
    label = None
    first, last = [], []
    for field, _w, raw_plane in _fields(buf):
        if field != 1:
            continue
        pname, lines, meta, stat_names = "", [], [], {}
        for f, _w2, v in _fields(raw_plane):
            if f == 2:
                pname = bytes(v).decode()
            elif f == 3:
                lines.append(v)
            elif f == 4:
                meta.append(v)
            elif f == 5:
                key, msg = _map_entry(v)
                for f2, _w3, v2 in _fields(msg):
                    if f2 == 2:
                        stat_names[key] = bytes(v2).decode()
        if pname.startswith("/device:TPU:"):
            for raw in lines:
                lname, t0, events = _line_events(raw)
                if lname != "XLA Ops" or not events:
                    continue
                ivals = []
                for ev in events:
                    off = dur = 0
                    for f3, _w3, v3 in _fields(ev):
                        if f3 == 2:
                            off = _signed(v3)
                        elif f3 == 3:
                            dur = _signed(v3)
                    ivals.append((t0 + off, t0 + off + dur))
                busy.append(union(ivals))
            continue
        if not pname.startswith("/host:"):
            continue
        names: Dict[int, str] = {}
        for entry in meta:
            key, msg = _map_entry(entry)
            for f2, _w3, v2 in _fields(msg):
                if f2 == 2:
                    names[key] = bytes(v2).decode("utf-8", "replace")
        labels = {m.group(1) for m in map(_TRAIN_PHASE.match,
                                          names.values()) if m}
        if labels:
            label = sorted(labels)[0]
        wanted = {k: n for k, n in names.items()
                  if n == TICK or n in labels or _ENGINE.match(n)
                  or _TRAIN_PHASE.match(n)}
        for index, raw in enumerate(lines):
            lname, t0, events = _line_events(raw)
            for ev in events[:1] + events[-1:]:   # the session's extent
                f = {f3: v3 for f3, w3, v3 in _fields(ev) if w3 == 0}
                a = t0 + _signed(f.get(2, 0))
                first.append(a)
                last.append(a + _signed(f.get(3, 0)))
            if not wanted:
                continue
            thread = f"{pname}/{lname}#{index}"   # names can repeat
            for ev in events:
                mid = off = dur = 0
                raw_stats = []
                for f3, _w3, v3 in _fields(ev):
                    if f3 == 1:
                        mid = v3
                        if mid not in wanted:
                            break
                    elif f3 == 2:
                        off = _signed(v3)
                    elif f3 == 3:
                        dur = _signed(v3)
                    elif f3 == 4:
                        raw_stats.append(v3)
                if mid not in wanted:
                    continue
                sp = Span(wanted[mid], t0 + off, t0 + off + dur, thread,
                          dict(_stat(s, stat_names) for s in raw_stats))
                (ticks if sp.name == TICK else spans).append(sp)
    spans.sort(key=lambda s: (s.start_ps, -s.dur_ps))
    return Trace(spans, ticks, busy, label,
                 (min(first), max(last)) if first else None)


_CACHE: Dict[str, Optional[Trace]] = {}


def newest_trace() -> Optional[str]:
    found = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: Optional[str] = None) -> Optional[Trace]:
    """The trace at ``path`` (default: the run's own), parsed once per
    process; ``None`` if there is none or it cannot be read."""
    path = path or newest_trace()
    if path is None:
        return None
    path = os.path.abspath(path)
    if path not in _CACHE:
        try:
            _CACHE[path] = _parse(path)
        except Exception as e:  # noqa: BLE001 — a reader never raises
            print(f"spans: cannot read {path}: {e!r}", file=sys.stderr)
            _CACHE[path] = None
    return _CACHE[path]


# ----------------------------------------------------------------- phases
def _phase_key(trace: Trace, name: str) -> str:
    """The key of a span's name in a phase map."""
    if name.startswith("infer/"):
        return name
    return "step" if name == trace.train_label else name.rpartition("/")[2]


def segments(trace: Trace, phases) -> List[Tuple[int, int, str]]:
    """Disjoint (start, end, phase) pieces of the time under the spans
    a phase map knows, each named by the innermost span covering it.
    Spans of every thread go into one timeline: ticks follow each other
    even where the executor moves them between threads."""
    mine = [(s.start_ps, s.end_ps, phases[_phase_key(trace, s.name)])
            for s in trace.spans if _phase_key(trace, s.name) in phases]
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, int, str]] = []
    cursor = 0

    def close(until: int):
        nonlocal cursor
        while stack and stack[-1][1] <= until:
            _a, b, phase = stack.pop()
            if b > cursor:
                out.append((cursor, b, phase))
                cursor = b

    for a, b, phase in mine:
        close(a)
        if stack:
            b = min(b, stack[-1][1])    # a child never outlives its parent
            if a > cursor:
                out.append((cursor, a, stack[-1][2]))
        cursor = max(cursor, a)
        if b > cursor:
            stack.append((a, b, phase))
    close(1 << 62)
    return out


def _overlap_by_phase(idle: List[Interval],
                      segs: List[Tuple[int, int, str]]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                out[segs[k][2]] = out.get(segs[k][2], 0) + hi - lo
            k += 1
    return out


def idle_by_phase(trace: Optional[Trace], phases) -> Optional[Dict]:
    """Seconds of device idle time inside the ticks' window by phase,
    per device (averaged over the devices), with the window and the
    ticks they are to be divided by.  ``None`` without device planes,
    ticks, or the spans of this phase map."""
    if trace is None or not trace.device_busy:
        return None
    win = trace.window()
    segs = segments(trace, phases)
    if win is None or not segs:
        return None
    lo, hi, n_ticks = win
    sums = {phase: 0 for phase in phases.values()}
    idle_ps = 0
    for busy in trace.device_busy:
        idle = subtract([(lo, hi)], busy)
        idle_ps += total(idle)
        covered = _overlap_by_phase(idle, segs)
        for phase, ps in covered.items():
            sums[phase] += ps
        sums[phases[None]] += total(idle) - sum(covered.values())
    n_dev = len(trace.device_busy)
    return {"seconds": {k: v / n_dev / 1e12 for k, v in sums.items()},
            "idle_s": idle_ps / n_dev / 1e12,
            "window_s": (hi - lo) / 1e12, "ticks": n_ticks,
            "n_devices": n_dev}


def fanout_ms(trace: Optional[Trace]) -> Optional[float]:
    """Median duration of ``serve/fanout`` in the ticks' window."""
    win = trace.window() if trace is not None else None
    if win is None:
        return None
    durs = [s.dur_ps for s in trace.named("serve/fanout")
            if win[0] <= s.start_ps < win[1]]
    return statistics.median(durs) / 1e9 if durs else None


def tick_tails_ms(trace: Optional[Trace]) -> List[float]:
    """For every prefill in the trace, the end of the ``infer/step``
    around it minus its own end: how long its first token waits for the
    rest of the tick."""
    if trace is None:
        return []
    steps = trace.named("infer/step")
    out = []
    for p in trace.named("infer/prefill", "infer/prefill_cached"):
        around = [s for s in steps if s.thread == p.thread
                  and s.start_ps <= p.start_ps and p.end_ps <= s.end_ps]
        if around:
            out.append((around[0].end_ps - p.end_ps) / 1e9)
    return out


def read_metric(name: str, path: Optional[str] = None) -> Optional[float]:
    """A span-sourced per-layer metric by its reader's name; ``None``
    where the trace has nothing for it.  Never raises."""
    try:
        trace = load(path)
        if name in IDLE_READERS:
            phases, phase = IDLE_READERS[name]
            got = idle_by_phase(trace, phases)
            if got is None:
                return None
            return 1e3 * got["seconds"][phase] / got["ticks"]
        if name == "fanout_ms_per_tick":
            return fanout_ms(trace)
        if name == "tick_tail_ms":
            tails = tick_tails_ms(trace)
            return statistics.median(tails) if tails else None
    except Exception as e:  # noqa: BLE001 — a reader never raises
        print(f"spans: {name}: {e!r}", file=sys.stderr)
    return None


def summary(path: Optional[str] = None) -> Dict[str, object]:
    """Everything this module reads from one trace, for PERF.md."""
    trace = load(path)
    if trace is None:
        return {}
    steps = trace.named("infer/step")
    every = trace.spans + trace.ticks
    out: Dict[str, object] = {
        # where the program's spans sit inside the profiler's session:
        # ticks missing at an edge are a stall of the process there
        "session_ms": (trace.extent[1] - trace.extent[0]) / 1e9
        if trace.extent else None,
        "first_span_after_ms": (min(s.start_ps for s in every)
                                - trace.extent[0]) / 1e9
        if trace.extent and every else None,
        "last_span_before_ms": (trace.extent[1]
                                - max(s.end_ps for s in every)) / 1e9
        if trace.extent and every else None,
        "spans": {n: sum(1 for s in trace.spans if s.name == n)
                  for n in sorted({s.name for s in trace.spans})},
        "ticks": len(trace.ticks),
        "tick_threads": len({t.thread for t in trace.ticks}),
        "devices": len(trace.device_busy),
        "train_label": trace.train_label,
        # durations inside the traced piece, to set against the same
        # quantities of the untraced window (what the profiler costs)
        "median_ms": {n: statistics.median(
            s.dur_ps for s in trace.spans + trace.ticks if s.name == n) / 1e9
            for n in sorted({s.name for s in trace.spans + trace.ticks})},
        "fanout_ms_per_tick": fanout_ms(trace),
        "tick_tails_ms": tick_tails_ms(trace),
        # the serve front's share of each tick-to-tick period
        "between_step_gaps_ms": [
            (b.start_ps - a.end_ps) / 1e9 for a, b in zip(steps, steps[1:])],
        "step_ms": [s.dur_ps / 1e9 for s in steps],
    }
    for key, phases in (("serve", SERVE_PHASES), ("train", TRAIN_PHASES)):
        got = idle_by_phase(trace, phases)
        if got:
            got["ms_per_tick"] = {k: 1e3 * v / got["ticks"]
                                  for k, v in got["seconds"].items()}
            out[key] = got
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(summary(sys.argv[1] if len(sys.argv) > 1 else None)))
