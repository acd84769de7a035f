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
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_lock = threading.Lock()
_enabled = False
_tracer = None          # otel tracer when available
_records: List[Dict[str, Any]] = []   # fallback recorder
_MAX_RECORDS = 10_000


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
    """Fallback-recorder contents (OTel-less environments/tests)."""
    with _lock:
        return list(_records)


def clear_recorded() -> None:
    with _lock:
        _records.clear()


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
        if _enabled:
            if _tracer is not None:
                self._otel = _tracer.start_as_current_span(self.name)
                self._otel_span = self._otel.__enter__()
                _otel_attributes(self._otel_span, self.attributes)
            else:
                self._rec = {"name": self.name, "start": time.time(),
                             "tid": threading.get_ident(),
                             "attributes": self.attributes}
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
            with _lock:
                _records.append(rec)
                if len(_records) > _MAX_RECORDS:
                    del _records[:len(_records) - _MAX_RECORDS]


def span(name: str, **attributes) -> Span:
    """Trace one operation: the one way the program opens a span.

    Where ``jax`` is already imported (never imported from here:
    control-plane processes stay free of it) the span is a
    ``jax.profiler.TraceAnnotation`` on the profiler's clock, so a
    device trace shows what the host was doing; with no profile running
    that is a flag check.  With :func:`enable_tracing` on it is also an
    OTel span or a fallback record, as before.  Attributes are plain
    ``str``/``int``/``float``.

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
