"""``attn_glue_ms_per_step``: what the reader's patterns take of a train
step's operations and what they leave, by the jax names the chip's
traces give them (one chip, under ``shard_map``, the routed model's two
layer kinds)."""

import pytest

from benchmark.harness import common, metrics

ATTN_F, ATTN_B = "jit(step)/jvp(gpt/attn)/", "jit(step)/transpose(jvp(gpt/attn))/"

# name -> seconds in a window of four steps; True: glue
OPS = {
    # the dispatchers' own work around their kernels
    ATTN_B + "attn/pack2/mul": (0.008, True),
    ATTN_B + "attn/pack2/broadcast_in_dim": (0.012, True),
    ATTN_B + "attn/pack2/reduce_sum": (0.004, True),
    ATTN_B + "attn/pack2/reshape": (0.004, True),
    ATTN_F + "attn/pack2/transpose": (0.004, True),
    ATTN_B + "shard_map/attn/pack2/broadcast_in_dim": (0.012, True),
    ATTN_B + "window/attn/flash/broadcast_in_dim": (0.008, True),
    ATTN_F + "attn/flash/slice": (0.004, True),
    # the layout changes directly under the model's attention scope,
    # alone, fused with each other, and under shard_map
    ATTN_B + "transpose": (0.012, True),
    ATTN_F + "reshape": (0.008, True),
    ATTN_F + "transpose;" + ATTN_F + "reshape": (0.004, True),
    ATTN_B + "shard_map/transpose": (0.012, True),
    ATTN_F + "shard_map/reshape": (0.008, True),
    # the kernels, the projections, the norm, the gradients' sums
    ATTN_B + "attn/pack2/pallas_call": (0.064, False),
    ATTN_F + "shard_map/attn/pack2/pallas_call": (0.044, False),
    ATTN_B + "window/attn/flash/pallas_call": (0.080, False),
    ATTN_B + "bsd,dhk->bshk/dot_general": (0.048, False),
    ATTN_B + "transpose(jvp(bshk,hkd->bsd))/dot_general": (0.016, False),
    ATTN_F + "jvp(bshk,hkd->bsd)/dot_general": (0.008, False),
    ATTN_F + "reduce_sum": (0.001, False),
    ATTN_B + "add_any": (0.004, False),
    ATTN_B + "transpose(jvp())/add_any": (0.002, False),
    # other layers, other executables
    "jit(step)/transpose(jvp(gpt/ffn))/bsd,df->bsf/dot_general": (0.09, False),
    "jit(step)/jvp(gpt/ffn)/transpose": (0.002, False),
    "jit(step)/jvp(gpt/ffn)/moe/experts/reshape": (0.002, False),
    "jit(step)/jvp(gpt/ce)/reduce_sum": (0.026, False),
    "jit(step)/transpose(jvp(gpt/ce))/transpose": (0.002, False),
    "jit(prefill)/gpt/attn/attn/pack2/transpose": (0.5, False),
}


def _read(ops):
    trace = {"modules": {"jit_step": {"calls": 4, "seconds": 1.0}},
             "op_seconds": ops}
    return metrics.read_layer_metric("attn_glue_ms_per_step",
                                     {"trace": trace, "facts": {}})


def test_patterns_take_the_glue_and_leave_kernels_matmuls_and_other_layers():
    want = 1e3 * sum(s for s, glue in OPS.values() if glue) / 4
    assert _read({k: s for k, (s, _) in OPS.items()}) == pytest.approx(want)
    for name, (s, glue) in OPS.items():
        assert _read({name: s}) == pytest.approx(1e3 * s / 4 if glue else 0.0), name


def test_it_is_a_train_kernels_metric_of_the_three_train_cells():
    man = common.manifest()
    entry = next(m for m in man["per_layer"]
                 if m["name"] == "attn_glue_ms_per_step")
    trains = next(m for m in man["end_to_end"]
                  if m["name"] == "train_tok_s_chip")["workloads"]
    assert entry["workloads"] == trains and len(trains) == 3
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "train kernels", "train_tok_s_chip", "device_trace")
    # a trace in which no step ran is unreadable, not a zero
    assert metrics.read_layer_metric(
        "attn_glue_ms_per_step",
        {"trace": {"modules": {}, "op_seconds": {}}, "facts": {}}) is None
