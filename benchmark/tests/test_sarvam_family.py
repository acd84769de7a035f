"""The ``sarvam`` family's files, found by name from the configuration:
manifest, family lookup, costs by hand, the new readers on made-up
traces, the control's rehearsal on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common, family, metrics

CELL = "serve-sarvam-105b-ep4-docs"
CONF = common.load_json(common.BENCH_DIR + "/configs/sarvam-105b-ep4.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["moe_ms_per_tick.batch", "moe_held_picks_per_tok.batch",
       "moe_experts_hit_per_call.batch", "latent_attn_roofline.batch",
       "latent_prefill_attn_roofline.batch", "prefill_ms_per_req.batch",
       "moe_shared_ms_per_tick.batch", "moe_loop_trips_per_call.batch",
       "moe_decode_roofline.batch"]


def test_cell_finds_its_files_and_its_rehearsal():
    files = common.cell_files(CELL)
    mix = files["traffic"]
    assert {k: mix[k] for k in (
        "kind", "clients_per_slot", "system_prompt_tokens",
        "requests_per_client", "max_total_tokens", "ramp_until_finished",
        "ramp_limit_s", "correct_sample", "correct_new_tokens")} == {
        "kind": "closed_loop", "clients_per_slot": 2,
        "system_prompt_tokens": 0, "requests_per_client": 12,
        "max_total_tokens": 8192, "ramp_until_finished": 16,
        "ramp_limit_s": 180, "correct_sample": 2, "correct_new_tokens": 32}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.6, "min": 512, "max": 6144}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 320,
                                    "sigma": 0.5, "min": 96, "max": 1024}
    engine = files["sizing"]["engine"]
    assert (engine["slots"], engine["page_size"], engine["num_pages"]) == (
        16, 128, 16 * 64 + 1)
    assert len(engine["buckets"]) <= 6 and engine["buckets"][-1] == 6144
    assert all(b % 128 == 0 for b in engine["buckets"])
    assert {m["name"] for m in files["end_to_end"]} == {"serve_out_tok_s",
                                                        "setup_s"}
    layer = {m["name"] for m in files["per_layer"]}
    assert set(NEW) <= layer and "worker_ready_s" in layer
    assert not {"kv_pool_copy_ms_per_tick.batch",
                "decode_layout_copy_ms_per_tick.batch"} & layer
    assert all(m["moves"] in ("serve_out_tok_s", "setup_s")
               for m in files["per_layer"])
    toy = common.cell_files(CELL, rehearsal=True)
    assert toy["config"]["name"] == "sarvam.rehearsal"
    assert toy["config"]["model"]["preset"] == "sarvam_tiny"
    man = common.manifest()
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    # its place, not "the last": a later cell comes behind it
    assert cell["chips"] == 1 and man["workloads"].index(cell) == 7
    assert [c["name"] for c in man["configs"]].index(CONF["name"]) == 4
    assert [m["name"] for m in man["per_layer"]
            if m.get("workloads") == [CELL]] == NEW
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] == [
        "train-gpt2-124m-dp4"]
    # and nothing it brings reads in the routed train cell
    train = common.cell_files("train-mellum2-12b-a2.5b-ep4-b2x8192")
    assert not set(NEW) & {m["name"] for m in train["per_layer"]}


def test_every_prompt_of_the_mix_has_a_bucket_and_no_page_is_shared():
    from benchmark.harness import traffic
    files = common.cell_files(CELL)
    plan = traffic.closed_loop_requests(files["traffic"], 4800002115, 32,
                                        65536)
    buckets = files["sizing"]["engine"]["buckets"]
    lengths = sorted({len(r.prompt) for r in plan["requests"]})
    assert len(lengths) == 16 and lengths[0] >= 512
    assert lengths[-1] == buckets[-1] == 6144
    assert len({r.prompt[0] for r in plan["requests"]}) == 32 * 12
    assert all(len(r.prompt) + r.max_new_tokens <= 8192
               and r.max_new_tokens >= 96 for r in plan["requests"])
    # every wave of 16 is the same multiset of work
    first = sorted(len(r.prompt) for r in plan["requests"][:16])
    assert first == lengths
    shapes = traffic.warmup_shapes(plan["requests"], 128, buckets)
    assert len(shapes) == len(buckets)      # one cold executable each


def test_family_brings_reference_tolerances_costs_and_rehearsal():
    assert family.family_of(CONF) == "sarvam"
    family.require(CONF)
    ref = family.reference("sarvam")
    assert set(ref.FAULTS) == {
        "", "no_shared", "softmax", "no_renorm", "no_yarn_scale",
        "no_q_norm", "wrong_held", "router_bf16"}
    assert family.tolerances(ref, logits_tol=1.0) == {
        "logits_tol": ref.LOGITS_TOL}
    assert 0 < ref.CHOICE_MARGIN < 0.1 and callable(ref.decided_rows)
    assert family.costs("sarvam").__name__.endswith("costs_sarvam")
    assert os.path.basename(family.rehearsal_file(CONF)) == \
        "sarvam.rehearsal.json"
    import inspect
    src = inspect.getsource(ref)
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_configuration_file_is_the_catalogs_config_but_for_the_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
    assert CONF["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONF.get(k) != v}
    assert differs == set(CONF["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in CONF["reduced"]:
        assert CONF["published"][key] == row["config"][key]
    kwargs = CONF["model"]["kwargs"]
    assert kwargs["held_experts"] == list(range(32))
    assert (kwargs["n_layers"], kwargs["vocab_size"], kwargs["max_seq"]) \
        == (6, 65536, 8192)
    assert CONF["deployment"]["chips_sharing_a_layer"] == 4
    assert "not_run" in CONF["deployment"]
    for key in ("reduced_how", "assumed", "memory", "deployment"):
        assert CONF[key]
    assert all(set(a) == {"what", "cost"} for a in CONF["assumed"])
    man = common.manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONF["name"])
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_costs_by_hand():
    c = family.costs("sarvam")
    m = c.model_dims(CONF)
    assert (m["d"], m["L"], m["dense_layers"], m["routed_layers"], m["H"],
            m["row"], m["fe"], m["fs"], m["held"], m["experts"],
            m["top_k"], m["V"]) == (4096, 6, 1, 5, 64, 576, 2048, 2048, 32,
                                    128, 8, 65536)
    # a decode of 16 rows at 2,500 tokens each: 40,000 rows of 1152 B a
    # layer, both halves of W_kvb (16.8 MB) a layer
    attn = c.decode_attention_cost(CONF, 40000.0, 16.0)
    w_kvb = 64 * 512 * 256
    assert attn["bytes"] == 6 * 2 * (40000 * 576 + 16 * 64 * (576 + 512)
                                     + w_kvb)
    assert attn["flops"] == 6 * (40000 * 64 * (576 + 512) * 2
                                 + 16 * w_kvb * 2)
    assert attn["bytes"] / 819e9 > attn["flops"] / 197e12   # memory-bound
    fill = c.prefill_attention_cost(CONF, 2304.0, 0.0)
    assert fill["flops"] == 6 * (2304 * 2305 / 2) * 64 * 2 * 320
    # 20 of 32 held experts hit in each of 5 layers by 32 held picks a
    # layer: 100 x 50.3 MB read, 160 picks x 50.3 MFLOP
    moe = c.decode_moe_cost(CONF, 100.0, 160.0)
    assert moe["bytes"] == 100 * 3 * 4096 * 2048 * 2
    assert moe["flops"] == 160 * 3 * 4096 * 2048 * 2
    assert moe["bytes"] / 819e9 == pytest.approx(6.14e-3, rel=1e-2)
    assert moe["bytes"] / 819e9 > 100 * moe["flops"] / 197e12


def _made_up(monkeypatch, fetches):
    from benchmark.reduce import spans
    trace = spans.Trace(
        [spans.Span("infer/sample", i, i + 1, "t", st)
         for i, st in enumerate(fetches)], [], [], None)
    monkeypatch.setattr(spans, "load", lambda path=None: trace)


def test_new_readers_read_made_up_traces_and_nothing_from_a_parent(
        monkeypatch):
    body = "jit(decode)/while/body/closed_call/moe/"
    trace = {"modules": {"jit_decode": {"calls": 10, "seconds": 0.11},
                         "jit_prefill": {"calls": 2, "seconds": 0.2}},
             "op_seconds": {body + "experts/while/body/dot_general": 0.06,
                            body + "route/top_k": 0.005,
                            body + "shared/dot_general": 0.01,
                            "jit(prefill)/while/body/closed_call/moe/"
                            "experts/while/body/dot_general": 0.05},
             "busy_s": 0.4, "window_s": 0.5, "ticks": 12, "n_devices": 1}
    ctx = {"facts": {"telemetry.moe.decode_loop_trips": 2100,
                     "telemetry.moe.decode_experts_hit": 2000,
                     "telemetry.moe.decode_calls": 100,
                     "telemetry.moe.held_picks": 9000,
                     "telemetry.moe.rows": 4500},
           "trace": trace, "config": CONF, "device_kind": "TPU v5 lite"}
    read = metrics.read_layer_metric
    assert read("moe_ms_per_tick.batch", ctx) == pytest.approx(7.5)
    assert read("moe_shared_ms_per_tick.batch", ctx) == pytest.approx(1.0)
    assert read("moe_loop_trips_per_call.batch", ctx) == 21.0
    assert read("moe_experts_hit_per_call.batch", ctx) == 20.0
    assert read("moe_held_picks_per_tok.batch", ctx) == 2.0
    assert read("prefill_ms_per_req.batch", ctx) == pytest.approx(100.0)
    decode = {"kind": "decode", "moe_hit": 100, "moe_held": 160,
              "moe_trips": 100, "rows": 16}
    _made_up(monkeypatch, [decode, dict(decode, moe_hit=90), {
        "kind": "prefill", "moe_hit": 160, "moe_held": 4000, "rows": 1}])
    least = 95 * 3 * 4096 * 2048 * 2 / 819e9
    assert read("moe_decode_roofline.batch", ctx) == pytest.approx(
        100 * least / 6.5e-3)
    assert 0 < read("moe_decode_roofline.batch", ctx) < 100
    # a parent's program: fetch spans with no kind (or no counts), no
    # shared scope, no seventh count -> nothing, and no error
    _made_up(monkeypatch, [{"moe_hit": 100, "moe_held": 160, "rows": 16}])
    assert read("moe_decode_roofline.batch", ctx) is None
    _made_up(monkeypatch, [{"rows": 16}])
    assert read("moe_decode_roofline.batch", ctx) is None
    bare = dict(ctx, facts={})
    assert read("moe_loop_trips_per_call.batch", bare) is None
    assert read("moe_decode_roofline.batch", dict(ctx, trace=None)) is None
    # a family that prices no decode's experts reads nothing
    gpt = common.load_json(common.BENCH_DIR + "/configs/gpt2-124m.json")
    _made_up(monkeypatch, [decode])
    assert read("moe_decode_roofline.batch", dict(ctx, config=gpt)) is None


def test_reference_at_the_rehearsal_size_against_the_tiny_preset():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.models import sarvam
    toy = common.load_json(family.rehearsal_file(CONF))
    kwargs = dict(toy["model"]["kwargs"], dtype=jnp.float32)
    cfg = getattr(sarvam.SarvamConfig, toy["model"]["preset"])(**kwargs)
    params = sarvam.init_params(cfg, jax.random.PRNGKey(2))
    engine = InferenceEngine(cfg, params, debug_logits=True, slots=2,
                             page_size=16, buckets=(32,))
    prompt = list(range(3, 30))
    rid = engine.submit(prompt, max_new_tokens=4)
    generated = []
    while engine.has_work():
        generated += [int(ev[1]) for ev in engine.step() if ev[0] == rid]
    got = np.stack(engine.logits_trace.pop(rid))
    ref = family.reference("sarvam")
    full = np.asarray([prompt + generated[:-1]], np.int32)
    want = np.asarray(family.call(ref.logits_last, params, full, 4,
                                  config=toy)[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    decided = ref.decided_rows(params, full, 4, toy)
    assert decided.shape == (4,) and decided.dtype == bool


def test_the_control_walks_its_rehearsal_on_the_cpu():
    """``controls/sarvam_check.py --rehearse-on-cpu``: the cell's check
    through ``BenchReplica.bench_check`` at the toy shapes, clean and
    with one planted fault, exit 3 by design."""
    done = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "controls",
                                      "sarvam_check.py"),
         "--seed", "4800002115", "--rehearse-on-cpu", "--only", "no_renorm"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 3, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.strip().splitlines()]
    assert [ln.get("check") for ln in lines[:-1]] == ["clean", "no_renorm"]
    assert lines[0]["correct"] and not lines[1]["correct"]
    assert all(ln["as_it_has_to_be"] for ln in lines[:-1])
    assert lines[-1]["ok"] is True
