"""Device idle while the host is in ``<label>/sync``, per step."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_sync_ms_per_step")
