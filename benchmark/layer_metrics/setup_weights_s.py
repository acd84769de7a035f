"""The chip worker's ``setup/weights`` span: the dispatch of the weights' draw."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_weights_s", ctx)
