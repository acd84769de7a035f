"""The trace reduction, on a trace recorded on a TPU v5e: two steps of a
toy GPT (d 128, 2 layers, 2 x 256 tokens) through ``build_gpt_train``,
with one ``bench/put_batch`` host annotation a step
(``tests/data/toy_train_v5e.xplane.pb``, 1 MB)."""

import os

import pytest

from benchmark.reduce import trace, xplane

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "toy_train_v5e.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane.read_xplane(TRACE)


def test_planes_lines_and_clock(planes):
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    assert dev.stats["device_type_string"] == "TPU v5 Lite"
    assert [(ln.name, len(ln.events)) for ln in dev.lines][:4] == [
        ("Steps", 2), ("XLA Modules", 2), ("XLA Ops", 544),
        ("Async XLA Ops", 134)]
    mods = dev.line("XLA Modules").events
    assert mods[0].display.startswith("jit_step(")
    assert mods[0].start_ps == 41993339000 and mods[0].dur_ps == 76353750
    host = next(p for p in planes if p.name == "/host:CPU")
    py = host.line("python3")
    marks = [e for e in py.events if e.name == "bench/put_batch"]
    assert len(marks) == 2
    # host and device share a clock: each step's module starts after the
    # batch of that step was put
    assert marks[0].start_ps < mods[0].start_ps < marks[1].start_ps


def test_jax_names_come_from_the_metadata(planes):
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    names = {trace.family(e) for e in dev.line("XLA Ops").events}
    assert "jit(step)/jvp(gpt/attn)/attn/pack2/pallas_call" in names
    assert any("transpose(jvp(gpt/ce))" in n for n in names)
    assert trace.clean_name("jit(step)/jvp(gpt/attn)/attn/pack2/"
                            "pallas_call:") == \
        "jit_step_/jvp_gpt/attn_/attn/pack2/pallas_call"


def test_reduction_numbers():
    r = trace.reduce_trace(TRACE)
    # no bench/tick in this recording: the window is the span of the
    # device operations, two executions of jit_step
    assert r["ticks"] == 0 and r["n_devices"] == 1
    assert r["modules"]["jit_step"]["calls"] == 2
    assert r["modules"]["jit_step"]["seconds"] == pytest.approx(
        (76353750 + 76708594) / 1e12)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(2.594416e-3, rel=1e-4)
    # the two steps are 76 us each in a 2.6 ms window: the chip idles
    assert 1 - r["busy_s"] / r["window_s"] > 0.9
    assert sum(r["op_seconds"].values()) <= r["busy_s"] * 1.0001
    assert trace.seconds_matching(r, ["attn/pack2/pallas_call"]) == \
        pytest.approx(2.988742e-05, rel=1e-3)
    b = trace.breakdown(r)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    assert b["device_ops"][0][0] == \
        "jit_step_/jvp_gpt/attn_/attn/pack2/pallas_call"
    assert sum(s for _n, s in b["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.05)


def test_interval_arithmetic():
    u = trace.union([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert u == [(0, 4), (5, 9)] and trace.total(u) == 8
    assert trace.subtract([(0, 10)], u) == [(4, 5), (9, 10)]
    assert trace.subtract(u, [(1, 6)]) == [(0, 1), (6, 9)]
