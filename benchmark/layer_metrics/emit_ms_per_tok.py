"""Median duration of ``serve/emit``: what one streamed token holds the
event loop for."""

from benchmark.reduce import front


def read(ctx):
    return front.read_metric("emit_ms_per_tok")
