"""Seconds of obtaining executables the persistent compile cache held, in the
chip worker's set-up."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_load_s", ctx)
