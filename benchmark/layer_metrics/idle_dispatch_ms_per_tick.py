"""Device idle while the host builds arguments and dispatches a prefill,
decode or verify (outside ``infer/sample``), per tick."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_dispatch_ms_per_tick")
