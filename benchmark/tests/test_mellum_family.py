"""The ``mellum`` family's files, found by name from the configuration:
manifest, family lookup, costs by hand, the reference at the rehearsal
size against the program's tiny preset."""

import os

import pytest

from benchmark.harness import common, family, metrics

CELL = "train-mellum2-12b-a2.5b-ep4-b2x8192"
CONF = common.load_json(
    common.BENCH_DIR + "/configs/mellum2-12b-a2.5b-ep4.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_cell_finds_its_files_and_its_rehearsal():
    files = common.cell_files(CELL)
    assert files["traffic"]["kind"] == "train_steps"
    assert (files["traffic"]["batch_per_chip"],
            files["traffic"]["seq"]) == (2, 8192)
    assert set(files["sizing"]["train"]["kwargs"]) == {
        "remat", "unroll_layers", "ce_chunk", "warmup_steps"}
    ends = {m["name"] for m in files["end_to_end"]}
    assert ends == {"train_tok_s_chip", "setup_s"}
    layer = {m["name"] for m in files["per_layer"]}
    assert {"mfu", "train_attn_roofline", "train_moe_roofline",
            "moe_ms_per_step", "attn_window_ms_per_step",
            "device_idle_share.train"} <= layer
    assert "norm_ms_per_step" not in layer
    toy = common.cell_files(CELL, rehearsal=True)
    assert toy["config"]["name"] == "mellum.rehearsal"
    assert toy["config"]["model"]["preset"] == "mellum_tiny"
    man = common.manifest()
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and man["workloads"][-1] is cell
    new = [m for m in man["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "moe_ms_per_step", "attn_window_ms_per_step", "train_moe_roofline"]
    assert sum(CELL in m.get("workloads", []) for m in man["per_layer"]) \
        == 13


def test_family_brings_reference_tolerances_costs_and_rehearsal():
    assert family.family_of(CONF) == "mellum"
    family.require(CONF)
    ref = family.reference("mellum")
    assert {"window_full", "no_yarn", "no_renorm", "no_held", "kv_mod",
            "router_bf16"} == set(ref.FAULTS)
    tol = family.tolerances(ref, loss_rtol=1.0, grad_norm_rtol=1.0)
    assert tol == {"loss_rtol": ref.LOSS_RTOL,
                   "grad_norm_rtol": ref.GRAD_NORM_RTOL}
    assert family.costs("mellum").__name__.endswith("costs_mellum")
    assert os.path.basename(family.rehearsal_file(CONF)) == \
        "mellum.rehearsal.json"


def test_configuration_file_is_the_catalogs_config_but_for_the_cut():
    import json
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CONF["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONF.get(k) != v}
    assert differs == set(CONF["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in CONF["reduced"]:
        assert CONF["published"][key] == row["config"][key]
    assert CONF["model"]["kwargs"]["held_experts"] == list(range(16))
    assert CONF["deployment"]["chips_sharing_a_layer"] == 4
    for key in ("reduced_how", "assumed", "memory", "deployment"):
        assert CONF[key]


def test_costs_by_hand():
    c = family.costs("mellum")
    m = c.model_dims(CONF)
    assert (m["d"], m["H"], m["KV"], m["hd"], m["fe"], m["held"],
            m["experts"], m["top_k"], m["V"]) == (
                2304, 32, 4, 128, 896, 16, 64, 8, 24576)
    assert m["kinds"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert c.visible_keys(8192, 1024) == pytest.approx(960.06, abs=0.01)
    assert c.visible_keys(512, 1024) == 256
    # a token, forward: projections 42.5 M a layer, pairs 67.1 M full and
    # 15.7 M window, router 0.29 M + 2 held picks x 12.4 M, head 113.2 M
    proj = 2 * 2304 * 128 * (32 + 8) + 2 * 4096 * 2304
    pairs = 4 * 4096 * (8192 / 2) + 3 * 4 * 4096 * c.visible_keys(8192, 1024)
    ffn = 2 * 2304 * 64 + 2 * 3 * 2 * 2304 * 896
    fwd = 4 * proj + pairs + 4 * ffn + 2 * 2304 * 24576
    assert fwd == pytest.approx(497.7e6, rel=1e-3)
    assert c.train_flops_per_token(CONF, 8192) == pytest.approx(3 * fwd)
    attn = c.train_attention_cost(CONF, 2, 8192)
    assert attn["flops"] == pytest.approx(2 * 8192 * pairs * 3.5)
    assert attn["bytes"] == 4 * 6 * 2 * 8192 * 128 * 2 * (32 + 4)
    moe = c.train_moe_cost(CONF, 16384)
    assert moe["flops"] == 4 * 32768 * 3 * 2 * 3 * 2304 * 896
    assert moe["flops"] / 197e12 > moe["bytes"] / 819e9   # compute-bound


def test_new_readers_read_made_up_traces_and_nothing_from_a_parent():
    # the grouped products reach the trace under the compiler's own name
    step = "ragged-dot-none"
    back = "jit(step)/transpose(jvp(gpt/ffn))/moe/experts/gather"
    trace = {"modules": {"jit_step": {"calls": 4, "seconds": 1.2}},
             "op_seconds": {
                 step: 0.04, back: 0.08,
                 "jit(step)/jvp(gpt/ffn)/moe/route/top_k": 0.004,
                 "jit(step)/jvp(gpt/attn)/window/attn/flash/pallas_call":
                     0.10,
                 "jit(step)/jvp(gpt/attn)/attn/flash/pallas_call": 0.12},
             "busy_s": 1.1, "window_s": 1.2, "ticks": 4, "n_devices": 1}
    ctx = {"facts": {"batch_per_chip": 2, "seq": 8192,
                     "train_tok_s_chip": 50000.0},
           "trace": trace, "config": CONF, "device_kind": "TPU v5 lite"}
    read = metrics.read_layer_metric
    assert read("moe_ms_per_step", ctx) == pytest.approx(31.0)
    assert read("attn_window_ms_per_step", ctx) == pytest.approx(25.0)
    assert read("attn_ms_per_step", ctx) == pytest.approx(55.0)
    cost = family.costs("mellum").train_moe_cost(CONF, 16384)
    least = cost["flops"] / 197e12
    assert read("train_moe_roofline", ctx) == pytest.approx(
        100 * least / 0.03)
    assert 0 < read("mfu", ctx) < 100
    assert 0 < read("train_attn_roofline", ctx) < 100
    # a program without the scopes (the parent commit): a trace_ms
    # pattern that matches nothing reads 0.0, the roofline reads nothing
    bare = dict(trace, op_seconds={
        "jit(step)/jvp(gpt/attn)/attn/flash/pallas_call": 0.12})
    ctx_bare = dict(ctx, trace=bare)
    assert read("moe_ms_per_step", ctx_bare) == 0.0
    assert read("train_moe_roofline", ctx_bare) is None
    # and a family that prices no expert layer reads nothing
    gpt = common.load_json(common.BENCH_DIR + "/configs/gpt2-124m.json")
    assert read("train_moe_roofline", dict(ctx, config=gpt)) is None


def test_reference_at_the_rehearsal_size_against_the_tiny_preset():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    toy = common.load_json(family.rehearsal_file(CONF))
    kwargs = dict(toy["model"]["kwargs"], dtype=jnp.float32)
    cfg = getattr(gpt.GPTConfig, toy["model"]["preset"])(**kwargs)
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 96), 0, 512)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    ref = family.reference("mellum")
    with jax.default_matmul_precision("highest"):
        want_loss, want_norm = family.call(
            ref.loss_and_grad_norm, params, tokens, batch["targets"], 2,
            config=toy)
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, batch, cfg))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g * g)
                              for g in jax.tree.leaves(grads))))
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert norm == pytest.approx(want_norm, rel=1e-4)
