"""Device idle under no program span (the caller's loop), per step."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("idle_outside_step_ms_per_step")
