"""The chip worker's ``setup/engine`` span: ``InferenceEngine.__init__``."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_engine_build_s", ctx)
