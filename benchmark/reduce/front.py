"""The serve front in a profiler trace: what the device's idle time
between two engine ticks passes under, and how long a streamed token and
a finished tick hold, or wait for, the replica's event loop.

``reduce/spans.py`` books every idle picosecond of the ticks' window to
the innermost ``infer/*`` span over it, and what lies under none of them
is its ``between_ticks``: one number for the serve front.  Two spans of
the program split it (``ray_tpu/inference/serve_gpt.py``):

- ``serve/emit``, one a streamed token, open from the deployment's
  ``yield`` until its consumer asks for the next item: the replica's
  re-yield, the serialisation and the object store's round trip of that
  token, on the event loop's thread;
- ``serve/fanout``, which carries ``more``: 0 where the pump found the
  engine without work after the fan-out and stops, so that until the next
  ``infer/step`` the replica has nothing to serve.

``split`` gives the three parts, which add up to ``between_ticks`` to the
picosecond: under a ``serve/emit`` of any thread (``emit``); after a
fan-out with ``more=0`` and under no ``serve/emit`` (``no_work``); the
rest (``other``: the fan-out itself, the executor hop, the loop's other
work, a finished tick waiting for the loop).  A trace of a program that
opens no ``serve/emit``, or whose fan-outs carry no ``more``, reads 0 for
that part and everything in ``other``, which is what such a program can
tell; only a trace without a whole engine tick or without a device plane
reads nothing.  Window, ticks and devices are ``spans.idle_by_phase``'s.
"""

from __future__ import annotations

import bisect
import statistics
import sys
from typing import Dict, Optional

from benchmark.reduce import spans
from benchmark.reduce.trace import subtract, total, union

# metric (without the suffix the manifest splits it by) -> part
IDLE_READERS = {"idle_emit_ms_per_tick": "emit",
                "idle_no_work_ms_per_tick": "no_work",
                "idle_front_other_ms_per_tick": "other"}


def split(trace: Optional[spans.Trace]) -> Optional[Dict]:
    """Picoseconds of between-tick device idle time by part, summed over
    the devices, with what they are to be divided by."""
    if trace is None or not trace.device_busy:
        return None
    win = trace.window()
    segs = spans.segments(trace, spans.SERVE_PHASES)
    if win is None or not segs:
        return None
    lo, hi, n_ticks = win
    in_tick = union([(a, b) for a, b, _phase in segs])
    emits = union([(s.start_ps, s.end_ps)
                   for s in trace.named("serve/emit")])
    starts = sorted(s.start_ps for s in trace.named("infer/step"))
    waits = []
    for f in trace.named("serve/fanout"):
        if f.stats.get("more") == 0:
            k = bisect.bisect_left(starts, f.end_ps)
            waits.append((f.end_ps, starts[k] if k < len(starts) else hi))
    no_work = subtract(union(waits), emits)
    ps = dict.fromkeys((*IDLE_READERS.values(), "between_ticks"), 0)
    for busy in trace.device_busy:
        between = subtract(subtract([(lo, hi)], busy), in_tick)
        whole = total(between)
        emit = whole - total(subtract(between, emits))
        waiting = whole - total(subtract(between, no_work))
        ps["emit"] += emit
        ps["no_work"] += waiting
        ps["other"] += whole - emit - waiting
        ps["between_ticks"] += whole
    return {"ps": ps, "ticks": n_ticks, "n_devices": len(trace.device_busy)}


def _in_window(trace: Optional[spans.Trace], name: str):
    """The spans of that name that start in the ticks' window; ``None``
    for a trace without an engine tick."""
    win = trace.window() if trace is not None else None
    if win is None or not trace.named("infer/step"):
        return None
    return [s for s in trace.named(name) if win[0] <= s.start_ps < win[1]]


def emit_ms(trace: Optional[spans.Trace]) -> Optional[float]:
    """Median duration of the ``serve/emit`` spans that start in the
    ticks' window; 0.0 where the program opens none."""
    emits = _in_window(trace, "serve/emit")
    if emits is None:
        return None
    return statistics.median(s.dur_ps for s in emits) / 1e9 if emits else 0.0


def pump_wait_ms(trace: Optional[spans.Trace]) -> Optional[float]:
    """Median, over the ``serve/fanout`` spans that start in the ticks'
    window, of their start minus the end of the ``infer/step`` that ended
    last before it: how long a finished tick's tokens waited for the
    event loop."""
    fans = _in_window(trace, "serve/fanout")
    if not fans:
        return None
    ends = sorted(s.end_ps for s in trace.named("infer/step"))
    waits = []
    for f in fans:
        k = bisect.bisect_right(ends, f.start_ps)
        if k:
            waits.append(f.start_ps - ends[k - 1])
    return statistics.median(waits) / 1e9 if waits else None


def read_metric(name: str, path: Optional[str] = None) -> Optional[float]:
    """A serve-front metric by its reader's name; never raises."""
    try:
        trace = spans.load(path)
        if name == "emit_ms_per_tok":
            return emit_ms(trace)
        if name == "pump_wait_ms_per_tick":
            return pump_wait_ms(trace)
        got = split(trace) if name in IDLE_READERS else None
        if got is None:
            return None
        seconds = got["ps"][IDLE_READERS[name]] / got["n_devices"] / 1e12
        return 1e3 * seconds / got["ticks"]
    except Exception as e:  # noqa: BLE001 — a reader never raises
        print(f"front: {name}: {e!r}", file=sys.stderr)
    return None


if __name__ == "__main__":
    import json
    path = sys.argv[1] if len(sys.argv) > 1 else None
    print(json.dumps({
        "split": split(spans.load(path)),
        **{n: read_metric(n, path) for n in (
            *IDLE_READERS, "emit_ms_per_tok", "pump_wait_ms_per_tick")}}))
