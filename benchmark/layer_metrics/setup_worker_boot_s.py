"""The chip worker's process start (``exec_epoch``) to the end of its
``setup/worker_boot``: interpreter, imports, registration."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_worker_boot_s", ctx)
