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
that can say is the one that compiled.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_LOCK = threading.Lock()
_WATCHING = False
_STATS = {"compiles": 0, "compile_seconds": 0.0,
          "cache_hits": 0, "cache_misses": 0}


_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


def _on_event(event: str, **_kw) -> None:
    counter = _CACHE_EVENTS.get(event)
    if counter is not None:
        with _LOCK:
            _STATS[counter] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    # one per executable jax obtains, from the compiler or the cache
    if event == "/jax/core/compile/backend_compile_duration":
        with _LOCK:
            _STATS["compiles"] += 1
            _STATS["compile_seconds"] += duration


def enable_compile_cache() -> str:
    """Make this process use the persistent compile cache and count its
    compiles; returns the cache directory.  Called wherever the program
    first compiles: the train-step builders, the inference engine, and a
    worker taking its chips.

    Where the CPU was asked for the cache stays off (``""``): it exists
    to save chip time, and the CPU test suite compiles toy shapes it has
    no reason to leave in the checkout."""
    global _WATCHING
    import jax
    with _LOCK:
        if not _WATCHING:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
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
    ran: executables obtained, seconds spent obtaining them, and the
    persistent cache's hits and misses among them."""
    with _LOCK:
        return dict(_STATS)
