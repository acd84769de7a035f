"""The manifest and the files it names: every cell finds its
configuration, its mix and its sizing, and every per-layer metric its
reader.  What a metric is stands in ``BENCHMARK.json`` alone."""

import os

import pytest

from benchmark.harness import common, metrics

MAN = common.manifest()


def test_every_cell_finds_its_files():
    for cell in MAN["workloads"]:
        files = common.cell_files(cell["name"])
        assert files["traffic"]["kind"] in ("train_steps", "open_loop",
                                            "closed_loop")
        key = "train" if files["traffic"]["kind"] == "train_steps" \
            else "engine"
        assert key in files["sizing"], cell["name"]
        assert files["end_to_end"] and files["per_layer"]
        toy = common.cell_files(cell["name"], rehearsal=True)
        assert key in toy["sizing"] and toy["config"]["n_embd"] == 128


def test_every_per_layer_metric_finds_a_reader_and_nothing_is_said_twice():
    ends = {m["name"] for m in MAN["end_to_end"]}
    used = set()
    for m in MAN["per_layer"]:
        stem = metrics.reader_file(m["name"])
        used.add(os.path.basename(stem))
        spec = common.load_json(stem + ".json")
        assert set(spec) == {"what", "reader"}, m["name"]
        if spec["reader"]["kind"] == "python":
            assert os.path.exists(stem + ".py")
        assert m["moves"] in ends
    on_disk = {f[:-5] for f in os.listdir(metrics.METRIC_DIR)
               if f.endswith(".json")}
    assert on_disk == used          # no reader that no metric reads


def test_python_readers_on_made_up_facts():
    conf = common.load_json(common.BENCH_DIR + "/configs/gpt2-large.json")
    trace = {"modules": {"jit_decode": {"calls": 10, "seconds": 4.0}},
             "op_seconds": {"jit(decode)/while/body/closed_call/attn/"
                            "decode_pallas/pallas_call": 1.0},
             "busy_s": 4.0, "window_s": 5.0, "ticks": 10, "n_devices": 1}
    ctx = {"facts": {"decode_context_tokens": 20000.0,
                     "decode_tok_per_step": 30.0},
           "trace": trace, "config": conf, "device_kind": "TPU v5 lite"}
    assert metrics.read_layer_metric("decode_step_ms.itl", ctx) == 400.0
    assert metrics.read_layer_metric("device_idle_share.batch", ctx) == \
        pytest.approx(20.0)
    ms = metrics.read_layer_metric("decode_attn_ms_per_tick.itl", ctx)
    share = metrics.read_layer_metric("decode_attn_roofline.itl", ctx)
    assert ms == pytest.approx(100.0) and 0 < share < 100
