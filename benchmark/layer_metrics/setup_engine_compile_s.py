"""Sum of the chip worker's ``infer/compile`` spans: the engine's executables."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_engine_compile_s", ctx)
