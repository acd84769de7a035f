"""Share of its roofline the decode attention over latent rows reaches:
the least time for the traced piece's own decodes (the context tokens and
rows each ``infer/decode`` span carries, priced by the family's
``decode_attention_cost``) over the device time the attention path took
in the same piece, both per decode.  A program whose decode spans carry no
``tokens`` reads nothing."""

from benchmark.harness import family, metrics
from benchmark.reduce import costs, spans


def read(ctx):
    ms = metrics.read_layer_metric("decode_attn_ms_per_tick", ctx)
    trace = spans.load()
    if not ms or trace is None:
        return None
    decodes = [s.stats for s in trace.named("infer/decode")
               if "tokens" in s.stats and "active" in s.stats]
    if not decodes:
        return None
    price = family.costs_for(ctx).decode_attention_cost
    each = [price(ctx["config"], float(st["tokens"]), float(st["active"]))
            for st in decodes]
    mean = {key: sum(c[key] for c in each) / len(each)
            for key in ("flops", "bytes")}
    return costs.roofline_percent(mean, ms / 1e3, ctx["device_kind"])
