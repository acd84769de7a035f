"""``setup/init``'s end to the start of the chip worker's first
``setup/actor_init`` / ``setup/task``: placement and the lease."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_worker_place_s", ctx)
