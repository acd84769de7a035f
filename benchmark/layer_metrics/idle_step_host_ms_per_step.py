"""Device idle while the host is in the step wrapper outside its sync,
per step."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_step_host_ms_per_step")
