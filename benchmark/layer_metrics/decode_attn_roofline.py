"""Share of its roofline the decode attention reaches: the least time to
read the K and V of the contexts live in an average decode dispatch of
the window, over the device time its kernel and layout ops took."""

from benchmark.harness import metrics
from benchmark.reduce import costs


def read(ctx):
    ms = metrics.read_layer_metric("decode_attn_ms_per_tick", ctx)
    facts = ctx["facts"]
    if not ms or not facts.get("decode_context_tokens"):
        return None
    cost = costs.decode_attention_cost(
        ctx["config"], facts["decode_context_tokens"],
        facts["decode_tok_per_step"])
    return costs.roofline_percent(cost, ms / 1e3, ctx["device_kind"])
