"""Share of its roofline the routed experts of a decode reach: the least
time for the traced piece's own decodes (the experts hit and the held
picks each decode's ``infer/sample`` span carries, priced by the
family's ``decode_moe_cost``) over the device time of everything under
``moe/`` less the shared expert's, both per decode.  A family that
prices no such thing, and a program whose fetch spans carry no step
kind or counts, read nothing."""

from benchmark.harness import family, metrics
from benchmark.reduce import costs, spans


def read(ctx):
    price = getattr(family.costs_for(ctx), "decode_moe_cost", None)
    trace = spans.load()
    if price is None or trace is None:
        return None
    ms = metrics.read_layer_metric("moe_ms_per_tick", ctx)
    shared = metrics.read_layer_metric("moe_shared_ms_per_tick", ctx)
    if not ms or shared is None or ms <= shared:
        return None
    decodes = [s.stats for s in trace.named("infer/sample")
               if s.stats.get("kind") == "decode" and "moe_hit" in s.stats
               and "moe_held" in s.stats]
    if not decodes:
        return None
    each = [price(ctx["config"], float(st["moe_hit"]),
                  float(st["moe_held"])) for st in decodes]
    mean = {key: sum(c[key] for c in each) / len(each)
            for key in ("flops", "bytes")}
    return costs.roofline_percent(mean, (ms - shared) / 1e3,
                                  ctx["device_kind"])
