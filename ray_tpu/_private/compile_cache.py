"""Where jax's persistent compilation cache lives — decided in one place.

A cold GPT-2 train step takes the TPU compiler most of a minute and the
serving engine compiles an executable per prefill bucket; a process that
finds them in the cache starts in seconds.  The cache's directory is
part of what makes an entry findable again, so it is never a temp name,
a pid or a timestamp: it is where ``JAX_COMPILATION_CACHE_DIR`` says
(jax reads that itself, and then nothing is set in code), and otherwise
``<checkout>/.jax_cache`` (git-ignored).  Worker processes resolve the
same path the same way, or inherit the variable from the driver.

The same call starts this process's compile counters
(:func:`compile_stats`): how many executables jax built, how long that
took, and how many came out of the persistent cache — the only process
that can say is the one that compiled.  And it puts jax's own time
spans into the start-up record (``util/tracing.py``), each with jax's
epoch start, its duration and the traced function's ``fun_name``:
``jax/trace`` (a function to a jaxpr), ``jax/lower`` (the jaxpr to an
MLIR module), and for the executable ``jax/load`` where the persistent
cache answered (``retrieval_s``: the read and deserialisation inside
it) or ``jax/compile`` where the compiler ran.  Records of traces
nest as the traces did; spans under a millisecond are summed
(:func:`compile_stats`) and not kept.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_LOCK = threading.Lock()
_WATCHING = False
_STATS = {"compiles": 0, "compile_seconds": 0.0,
          "cache_hits": 0, "cache_misses": 0,
          "trace_seconds": 0.0, "lower_seconds": 0.0,
          "load_seconds": 0.0}


_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_PARTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
# what the cache said inside the executable being obtained on this
# thread: its events fire before the backend span that holds them ends
_OPEN = threading.local()
# a time span shorter than this is summed and not kept as a record: a
# worker's set-up makes over a thousand (the jnp helpers a step's trace
# traces in passing), 0.2 s together, and the record is capped
_MIN_RECORD_S = 1e-3
# a jax without ``register_event_time_span_listener``: the duration
# listener makes the records, their end stamped at the callback
_SPANS_FROM_DURATIONS = False


def _on_event(event: str, **_kw) -> None:
    counter = _CACHE_EVENTS.get(event)
    if counter is not None:
        with _LOCK:
            _STATS[counter] += 1
        if counter == "cache_hits":
            _OPEN.hit = True


def _on_duration(event: str, duration: float, **kw) -> None:
    # one per executable jax obtains, from the compiler or the cache
    if event == _BACKEND:
        with _LOCK:
            _STATS["compiles"] += 1
            _STATS["compile_seconds"] += duration
    elif event == _RETRIEVAL:
        _OPEN.retrieval_s = duration
    if _SPANS_FROM_DURATIONS:
        now = time.time()
        _on_time_span(event, now - duration, now, **kw)


def _on_time_span(event: str, start: float, end: float,
                  fun_name: str = "", **_kw) -> None:
    from ray_tpu.util import tracing
    dur = end - start
    part = _PARTS.get(event)
    attributes = {"fun_name": fun_name}
    if event == _BACKEND:
        part = "load" if _OPEN.__dict__.pop("hit", False) else "compile"
        retrieval_s = _OPEN.__dict__.pop("retrieval_s", None)
        if part == "load":
            attributes["retrieval_s"] = retrieval_s
    if part is None:
        return
    inside = 0.0
    if part == "trace":
        # a jitted function traced inside another's trace (every jnp
        # helper of a step) ends first, and inside it: the sum counts
        # a second of tracing once
        tops = _OPEN.__dict__.setdefault("traces", [])
        while tops and tops[-1][0] >= start:
            inside += tops.pop()[1]
        tops.append((start, dur))
        if len(tops) > 4096:    # the oldest can be inside nothing to come
            del tops[:2048]
    if part != "compile":       # ``compile_seconds`` keeps its meaning
        with _LOCK:
            _STATS[part + "_seconds"] += dur - inside
    if dur >= _MIN_RECORD_S:
        tracing.keep("jax/" + part, start, dur, **attributes)


def enable_compile_cache() -> str:
    """Make this process use the persistent compile cache and count its
    compiles; returns the cache directory.  Called wherever the program
    first compiles: the train-step builders, the inference engine, and a
    worker taking its chips.

    Where the CPU was asked for the cache stays off (``""``): it exists
    to save chip time, and the CPU test suite compiles toy shapes it has
    no reason to leave in the checkout."""
    global _WATCHING, _SPANS_FROM_DURATIONS
    import jax
    with _LOCK:
        if not _WATCHING:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            spans = getattr(jax.monitoring,
                            "register_event_time_span_listener", None)
            if spans is not None:
                spans(_on_time_span)
            else:
                _SPANS_FROM_DURATIONS = True
            _WATCHING = True
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return ""
    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_stats() -> Dict[str, float]:
    """Compiles in this process since :func:`enable_compile_cache` first
    ran: executables obtained, seconds spent obtaining them
    (``load_seconds`` of them where the persistent cache answered), the
    cache's hits and misses among them, and the seconds jax spent
    tracing functions to jaxprs and lowering those to MLIR."""
    with _LOCK:
        return dict(_STATS)
