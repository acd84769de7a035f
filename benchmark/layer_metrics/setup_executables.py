"""How many executables the chip worker's set-up obtained."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_executables", ctx)
