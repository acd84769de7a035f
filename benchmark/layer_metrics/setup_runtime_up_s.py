"""ray_tpu.init(), whole: the ``setup/init`` span's duration."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_runtime_up_s", ctx)
