"""Share of its roofline the expert layers' grouped products reach: the
least time the chip could take for the held picks' three products,
forward and backward, at the cell's tokens a step (the family's
``train_moe_cost``), over the device time of everything under
``moe/experts`` in ``jit_step`` and of the grouped products'
own kernels, per step.  A family that prices no
expert layer, and a program without the scope, read nothing."""

from benchmark.harness import family, metrics
from benchmark.reduce import costs, trace as trace_mod

# the grouped products reach the trace as ``ragged-dot-none`` (the TPU
# compiler's rewrite strips their scope); what is around them (sort,
# gathers, the swiglu) keeps ``moe/experts``
PATTERNS = ["^jit\\(step\\)/.*moe/experts/", "^ragged-dot"]


def read(ctx):
    trace = ctx.get("trace")
    price = getattr(family.costs_for(ctx), "train_moe_cost", None)
    if not trace or price is None:
        return None
    steps, _ = metrics._module_calls(trace, "^jit_step")
    seconds = trace_mod.seconds_matching(trace, PATTERNS)
    if not steps or not seconds:
        return None
    facts = ctx["facts"]
    cost = price(ctx["config"], facts["batch_per_chip"] * facts["seq"])
    return costs.roofline_percent(cost, seconds / steps, ctx["device_kind"])
