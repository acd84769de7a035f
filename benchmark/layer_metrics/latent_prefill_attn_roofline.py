"""Share of its roofline a prefill's attention kernel over the gathered
latent context reaches: the least time for the traced piece's own
prefills (the bucket and the cached tokens each ``infer/prefill*`` span
carries, priced by the family's ``prefill_attention_cost``) over the
device time the kernel took in the same piece, both per prefill.  A
family without that cost, or a program without the kernel, reads
nothing."""

from benchmark.harness import family, metrics
from benchmark.reduce import costs, spans, trace as trace_mod

KERNEL = [r"^jit\(prefill(_cached)?\)/.*attn/prefill_pallas/"]


def read(ctx):
    trace = ctx.get("trace")
    price = getattr(family.costs_for(ctx), "prefill_attention_cost", None)
    if not trace or price is None:
        return None
    calls, _ = metrics._module_calls(trace, "^jit_prefill")
    seconds = trace_mod.seconds_matching(trace, KERNEL)
    spanned = spans.load()
    if not calls or not seconds or spanned is None:
        return None
    fills = [s.stats for s in spanned.named("infer/prefill",
                                            "infer/prefill_cached")
             if "bucket" in s.stats and "cached" in s.stats]
    if not fills:
        return None
    each = [price(ctx["config"], float(st["bucket"]), float(st["cached"]))
            for st in fills]
    mean = {key: sum(c[key] for c in each) / len(each)
            for key in ("flops", "bytes")}
    return costs.roofline_percent(mean, seconds / calls,
                                  ctx["device_kind"])
