"""How long a finished tick waited for the event loop."""

from benchmark.reduce import front


def read(ctx):
    return front.read_metric("pump_wait_ms_per_tick")
