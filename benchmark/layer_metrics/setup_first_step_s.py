"""The chip worker's ``setup/first_step`` record: the train step's first call."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_first_step_s", ctx)
