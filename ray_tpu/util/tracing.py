"""Tracing hooks (parity: ``python/ray/util/tracing/tracing_helper.py``).

The reference patches every remote call with OpenTelemetry spans when
``ray.init(_tracing_startup_hook=...)`` is set.  Here tracing is a
light seam over the same points: if ``opentelemetry`` is importable the
spans are real OTel spans (exported by whatever provider the user
configured); otherwise an in-process recorder keeps (name, start, end,
attributes) tuples so tests and the timeline can still observe the
graph.  Where jax is loaded every span is also a profiler
``TraceAnnotation``: one primitive, and the profiler's clock for the
spans a device trace is read against.

**The start-up record.**  A process comes up before anybody could enable
anything, so the spans of that path (names under ``setup/``, and
``infer/compile``, which opens on a miss only) are kept in the same
list whether or not tracing is on, with the ``pid`` and ``role`` of
their process; :func:`keep` adds what was timed elsewhere (jax's own
trace / lower / load time spans, ``_private/compile_cache.py``).  At
most ``_MAX_KEPT`` a process, then :func:`kept_stats` counts the
dropped.  Each is also appended, as it ends, as one JSON line to
``<session_dir>/logs/startup_<pid>.jsonl`` (held until the process knows
its session directory); ``ray_tpu.util.state.startup_timeline()`` reads
a session's files, and ``telemetry/chrome_trace.py`` exports the list as
it always did.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_lock = threading.Lock()
_enabled = False
_tracer = None          # otel tracer when available
_records: List[Dict[str, Any]] = []   # fallback recorder
_MAX_RECORDS = 10_000   # of the spans enable_tracing() adds
_TRIM_EVERY = 512       # ... trimmed once this many over

# the start-up record: kept whatever the flag says (module docstring)
_KEPT_NAMES = ("setup/", "infer/compile")
_MAX_KEPT = 2_048
_kept = 0               # kept records in ``_records``
_dropped = 0            # ... and those the cap turned away
_unwritten: List[Dict[str, Any]] = []   # kept, not yet in the file
_role = "driver"        # ``worker_proc.main`` says otherwise
_session_dir: Optional[str] = None
_out = None             # ((pid, session_dir), this process's open file)


def enable_tracing() -> bool:
    """Turn on span emission; True if real OpenTelemetry is active.

    The flag is process-local, so it is ALSO published to the control
    plane: worker processes check it at startup (``worker_proc``) and
    emit execute-side spans.  Workers already running before the enable
    keep tracing off until restarted (same init-time contract as the
    reference's ``_tracing_startup_hook``)."""
    _publish("1")
    global _enabled, _tracer
    with _lock:
        _enabled = True
        if _tracer is None:
            try:
                from opentelemetry import trace as otel_trace

                # only route spans to OTel when the user actually
                # configured a provider — the library default
                # (ProxyTracerProvider with no SDK behind it) swallows
                # spans silently, which would also starve the
                # in-process recorder that tests and the timeline read
                provider = otel_trace.get_tracer_provider()
                if type(provider).__name__ not in (
                        "ProxyTracerProvider", "NoOpTracerProvider"):
                    _tracer = otel_trace.get_tracer("ray_tpu")
            except Exception:  # noqa: BLE001 — recorder fallback
                _tracer = None
        return _tracer is not None


def disable_tracing() -> None:
    global _enabled
    _publish("0")
    with _lock:
        _enabled = False


_KV_KEY = b"__ray_tpu_tracing__"


def _publish(val: str) -> None:
    """Best-effort cluster-wide flag (no-op outside a ray_tpu session)."""
    try:
        from ray_tpu._private.worker import global_worker
        global_worker().cp.kv_put(_KV_KEY, val.encode(), True, "_sys")
    except Exception:  # noqa: BLE001 — local-only tracing still works
        pass


_cluster_cp = None
_cluster_checked = 0.0
_CLUSTER_TTL_S = 5.0


def maybe_enable_from_cluster(cp) -> None:
    """Worker-startup hook: adopt (and keep polling, via the TTL check
    in :func:`_refresh`) the cluster-wide tracing flag."""
    global _cluster_cp
    _cluster_cp = cp
    _refresh(force=True)


def _refresh(force: bool = False) -> None:
    """Re-read the cluster flag at most every ``_CLUSTER_TTL_S`` so an
    ``enable_tracing()`` on the driver reaches already-running workers
    within seconds (one KV read per worker per TTL — off the hot path
    unless tracing state actually changes anything)."""
    global _enabled, _cluster_checked
    if _cluster_cp is None:
        return
    now = time.monotonic()
    if not force and now - _cluster_checked < _CLUSTER_TTL_S:
        return
    _cluster_checked = now
    try:
        val = _cluster_cp.kv_get(_KV_KEY, namespace="_sys")
    except Exception:  # noqa: BLE001
        return
    if val == b"1" and not _enabled:
        with _lock:
            _enabled = True
    elif val == b"0" and _enabled:
        with _lock:
            _enabled = False


def is_enabled() -> bool:
    return _enabled


def recorded_spans() -> List[Dict[str, Any]]:
    """Fallback-recorder contents (OTel-less environments/tests): the
    start-up record, and what :func:`enable_tracing` added."""
    with _lock:
        return list(_records)


def clear_recorded(startup: bool = False) -> None:
    """Forget the spans :func:`enable_tracing` added; with ``startup``
    the start-up record too, and its count of dropped (its file stays
    as it is)."""
    global _kept, _dropped
    with _lock:
        if startup:
            _records.clear()
            del _unwritten[:]
            _kept = _dropped = 0
        else:
            _records[:] = [r for r in _records if "pid" in r]


def set_role(role: str) -> None:
    """What this process is (``driver``, ``worker``, ``node``): every
    kept record says it."""
    global _role
    _role = role


def use_session_dir(path: str) -> None:
    """The session whose ``logs/`` takes this process's start-up file
    from now on; what was kept before it was known is written there
    now.  A worker finds its own in ``RAY_TPU_SESSION_DIR``."""
    global _session_dir
    with _lock:
        _session_dir = path
        _write_kept()


def session_dir() -> Optional[str]:
    """The session directory last in use here (it outlives
    ``ray_tpu.shutdown()``, as the files do)."""
    return _session_dir or os.environ.get("RAY_TPU_SESSION_DIR") or None


def kept_stats() -> Dict[str, int]:
    """The start-up record's size in this process."""
    with _lock:
        return {"kept": _kept, "dropped": _dropped, "cap": _MAX_KEPT}


def _write_kept() -> None:
    """Append the kept records not yet written to this process's file
    (``_lock`` held), which stays open: an ``open`` a record was most of
    a record's cost, and a worker leaves through ``os._exit``, so each
    write is the unbuffered one line.  No session yet: they wait.  A
    directory that is gone: they are in the list and nowhere else."""
    global _out
    root = session_dir()
    if root is None or not _unwritten:
        return
    here = (os.getpid(), root)          # a forked child opens its own
    try:
        if _out is None or _out[0] != here:
            _out = (here, open(os.path.join(
                root, "logs", f"startup_{here[0]}.jsonl"), "ab",
                buffering=0))
        _out[1].write("".join(json.dumps(r, default=str) + "\n"
                              for r in _unwritten).encode())
    except OSError:
        _out = None
    del _unwritten[:]


def _append(rec: Dict[str, Any]) -> None:
    """One finished record into the one list; a kept one (it carries
    ``pid``) also into the file, and no trim ever takes it out."""
    global _kept, _dropped
    with _lock:
        if "pid" in rec:
            if _kept >= _MAX_KEPT:
                _dropped += 1
                return
            _kept += 1
            _records.append(rec)
            _unwritten.append(rec)
            _write_kept()
            return
        _records.append(rec)
        over = len(_records) - _kept - _MAX_RECORDS
        if over >= _TRIM_EVERY:
            # the oldest of what tracing added go, in one pass
            stay = []
            for r in _records:
                if over and "pid" not in r:
                    over -= 1
                else:
                    stay.append(r)
            _records[:] = stay


def keep(name: str, start: float, dur: float, **attributes) -> None:
    """Add to the start-up record a span that was timed elsewhere: jax's
    own time spans (``_private/compile_cache.py``), a train step's
    first record.  ``start`` is an epoch stamp, ``dur`` seconds."""
    _append({"name": name, "start": start, "dur": dur,
             "end": start + dur, "tid": threading.get_ident(),
             "attributes": attributes, "pid": os.getpid(),
             "role": _role})


def _otel_attributes(otel_span, attributes: Dict[str, Any]) -> None:
    for k, v in attributes.items():
        try:
            otel_span.set_attribute(k, v)
        except Exception:  # noqa: BLE001
            pass


class Span:
    """One open span: what :func:`span` returns and its ``with`` block
    binds.  ``start`` is the monotonic stamp at entry and ``dur`` the
    seconds to exit (``None`` until then), so a caller that also feeds
    another sink (the flight recorder, ``InferTelemetry``) takes both
    from here and reads no clock of its own."""

    __slots__ = ("name", "attributes", "start", "dur", "_ann", "_otel",
                 "_otel_span", "_rec")

    def __init__(self, name: str, attributes: Dict[str, Any]):
        self.name = name
        self.attributes = attributes
        self.start = 0.0
        self.dur: Optional[float] = None
        self._ann = self._otel = self._otel_span = self._rec = None

    @property
    def end(self) -> float:
        """Monotonic stamp of the exit (after it)."""
        return self.start + self.dur

    @property
    def recording(self) -> bool:
        """Whether a sink keeps this open span (a running profile, OTel,
        the fallback list): an attribute that costs something to compute
        is worth computing only then."""
        return (self._rec is not None or self._otel_span is not None
                or (self._ann is not None and self._ann.is_enabled()))

    def set(self, **attributes) -> None:
        """Attach what is known only now (a count at the span's end)."""
        self.attributes.update(attributes)
        if self._ann is not None:
            self._ann.set_metadata(**attributes)
        if self._otel_span is not None:
            _otel_attributes(self._otel_span, attributes)

    def __enter__(self) -> "Span":
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self.name,
                                                 **self.attributes)
            self._ann.__enter__()
        if _enabled and _tracer is not None:
            self._otel = _tracer.start_as_current_span(self.name)
            self._otel_span = self._otel.__enter__()
            _otel_attributes(self._otel_span, self.attributes)
        kept = self.name.startswith(_KEPT_NAMES)
        if kept or (_enabled and _tracer is None):
            self._rec = {"name": self.name, "start": time.time(),
                         "tid": threading.get_ident(),
                         "attributes": self.attributes}
            if kept:
                self._rec.update(pid=os.getpid(), role=_role)
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = time.monotonic() - self.start
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._otel is not None:
            self._otel.__exit__(*exc)
        rec = self._rec
        if rec is not None:
            rec["dur"] = self.dur
            rec["end"] = rec["start"] + self.dur
            _append(rec)


def span(name: str, **attributes) -> Span:
    """Trace one operation: the one way the program opens a span.

    Where ``jax`` is already imported (never imported from here:
    control-plane processes stay free of it) the span is a
    ``jax.profiler.TraceAnnotation`` on the profiler's clock, so a
    device trace shows what the host was doing; with no profile running
    that is a flag check.  With :func:`enable_tracing` on it is also an
    OTel span or a fallback record, as before; a span of the start-up
    path (``setup/...``, ``infer/compile``) is a record either way.
    Attributes are plain ``str``/``int``/``float``.

    The fallback record keeps an *epoch* ``start`` for timeline
    placement but takes ``dur`` (and the derived ``end``) from the
    monotonic clock: ``time.time()`` can step backwards under NTP
    slew, which used to yield negative/garbage durations for spans
    straddling a clock adjustment."""
    return Span(name, attributes)


def task_span(spec) -> "contextlib.AbstractContextManager":
    """Span for one task/actor-method execution (worker side)."""
    _refresh()
    if not _enabled:
        return contextlib.nullcontext()
    return span(
        f"task::{getattr(spec, 'name', '?')}",
        task_id=getattr(spec, 'task_id', b'').hex()[:16],
        actor_method=getattr(spec, 'actor_method', None) or "",
    )


def submit_span(name: str) -> "contextlib.AbstractContextManager":
    """Span for a submission on the caller side."""
    if not _enabled:
        return contextlib.nullcontext()
    return span(f"submit::{name}")
