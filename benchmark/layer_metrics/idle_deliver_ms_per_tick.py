"""Device idle while the host is in ``infer/deliver`` or elsewhere in
``infer/step`` outside its children, per tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_deliver_ms_per_tick")
