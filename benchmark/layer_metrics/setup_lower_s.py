"""Seconds of jax lowering jaxprs to MLIR modules in the chip worker's set-up."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_lower_s", ctx)
