"""Device idle between two ticks under no ``serve/emit`` and with work
left in the engine, per tick."""

from benchmark.reduce import front


def read(ctx):
    return front.read_metric("idle_front_other_ms_per_tick")
