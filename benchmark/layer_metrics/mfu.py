"""Model FLOP/s utilization of the window: tokens per second per chip
times the FLOPs one token's forward and backward need (recomputation not
counted), over the chip's bf16 peak."""

from benchmark.reduce import costs


def read(ctx):
    rate = ctx["facts"].get("train_tok_s_chip")
    if rate is None:
        return None
    return costs.mfu_percent(rate, ctx["config"], ctx["facts"]["seq"],
                             ctx["device_kind"])
