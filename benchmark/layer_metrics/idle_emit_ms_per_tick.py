"""Device idle between two ticks while a ``serve/emit`` is open, per tick."""

from benchmark.reduce import front


def read(ctx):
    return front.read_metric("idle_emit_ms_per_tick")
