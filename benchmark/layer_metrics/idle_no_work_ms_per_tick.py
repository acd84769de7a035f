"""Device idle between two ticks after the pump found the engine without
work, per tick."""

from benchmark.reduce import front


def read(ctx):
    return front.read_metric("idle_no_work_ms_per_tick")
