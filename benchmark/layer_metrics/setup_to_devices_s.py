"""The chip worker's first ``setup/actor_init`` / ``setup/task`` start to its
first ``jax.devices()``: ``import jax`` and libtpu's opening of the chip."""

from benchmark.reduce import startup


def read(ctx):
    return startup.read_metric("setup_to_devices_s", ctx)
