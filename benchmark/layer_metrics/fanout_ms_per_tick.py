"""Median duration of ``serve/fanout``."""

from benchmark.reduce import spans


def read(ctx):
    return spans.read_metric("fanout_ms_per_tick")
