"""Seconds of jax tracing functions to jaxprs in the chip worker's set-up."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_trace_s", ctx)
