"""Share of its roofline the step's attention kernels reach: the least
time the chip could take for the FLOPs and bytes causal attention needs
at the cell's shapes, over the device time the kernels took."""

from benchmark.harness import metrics
from benchmark.reduce import costs


def read(ctx):
    ms = metrics.read_layer_metric("attn_ms_per_step", ctx)
    if not ms:
        return None
    facts = ctx["facts"]
    cost = costs.train_attention_cost(ctx["config"],
                                      facts["batch_per_chip"], facts["seq"])
    return costs.roofline_percent(cost, ms / 1e3, ctx["device_kind"])
