"""How long a first token waits for the rest of its tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("tick_tail_ms")
