"""Device idle while the host is in ``infer/admit``, per tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_admit_ms_per_tick")
