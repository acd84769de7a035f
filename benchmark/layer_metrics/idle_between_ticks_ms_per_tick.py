"""Device idle between one ``infer/step`` and the next, per tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_between_ticks_ms_per_tick")
