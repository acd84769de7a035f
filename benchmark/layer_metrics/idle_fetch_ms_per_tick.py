"""Device idle while the host is in ``infer/sample``, per tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_fetch_ms_per_tick")
