"""Seconds of the compiler itself in the chip worker's set-up: warm, the small
executables the persistent cache does not take; cold, all of them."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_compile_s", ctx)
