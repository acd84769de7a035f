"""The span reader, on traces recorded on a TPU v5e with the program's
spans in them, and on the one-chip trace that predates the spans.

``data/toy_train_dp4_v5e.xplane.pb``: three steps of the toy GPT of
``configs/toy.rehearsal.json`` (d 128, 2 layers, 2 x 256 tokens a chip)
at dp=4 on the four-chip host, through ``build_gpt_train`` and its
``StepTelemetry`` wrapper, each step inside a ``bench/tick`` with the
batch's ``device_put`` and the caller's loss read, as
``harness/train_cell.py`` drives it.  ``data/toy_serve_v5e.xplane.pb``:
``BenchReplica`` on the ``tiny`` preset on one chip, two waves of three
streams through the serve pump, in-process.  Both with the Python
tracer off, which keeps them near the size of the old fixture."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common, metrics
from benchmark.reduce import spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN_DP4 = os.path.join(DATA, "toy_train_dp4_v5e.xplane.pb")
SERVE = os.path.join(DATA, "toy_serve_v5e.xplane.pb")
OLD = os.path.join(DATA, "toy_train_v5e.xplane.pb")
TRAIN_READERS = [n for n, (p, _k) in spans.IDLE_READERS.items()
                 if p is spans.TRAIN_PHASES]
SERVE_READERS = [n for n in spans.IDLE_READERS if n not in TRAIN_READERS] \
    + ["fanout_ms_per_tick", "tick_tail_ms"]
NEW = [m for m in common.manifest()["per_layer"]
       if os.path.basename(metrics.reader_file(m["name"]))
       in TRAIN_READERS + SERVE_READERS]


def _idle_ms_per_tick(path):
    r = trace.reduce_trace(path)
    return 1e3 * (r["window_s"] - r["busy_s"]) / r["ticks"], r


def test_every_new_entry_is_a_span_metric_with_cells_and_a_reader():
    assert len(NEW) == 16
    layers = {m["layer"] for m in common.manifest()["per_layer"]
              if m not in NEW}
    for m in NEW:
        assert m["source"] == "program_span" and m["workloads"], m["name"]
        assert m["layer"] in layers, m["name"]
        assert os.path.exists(metrics.reader_file(m["name"]) + ".py")


def test_train_readers_on_the_four_chip_trace_add_up_to_its_idle_time():
    want, r = _idle_ms_per_tick(TRAIN_DP4)
    assert r["n_devices"] == 4 and r["ticks"] == 3
    got = {n: spans.read_metric(n, path=TRAIN_DP4) for n in TRAIN_READERS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(want, rel=0.02)
    # a toy step leaves the chips idle nearly all the time, and most of it
    # while the host is inside the wrapper or between two steps
    assert got["idle_sync_ms_per_step"] > 0
    assert got["idle_step_host_ms_per_step"] > 0
    assert got["idle_outside_step_ms_per_step"] > 0
    t = spans.load(TRAIN_DP4)
    assert t.train_label == "train" and len(t.device_busy) == 4
    assert [s.stats["step_num"] for s in t.named("train")] == [4, 5, 6]
    assert len(t.named("train/loss_read")) == 3
    # a train trace holds no engine tick: the serve readers find nothing
    for n in SERVE_READERS:
        assert spans.read_metric(n, path=TRAIN_DP4) is None


def test_serve_readers_on_the_one_chip_trace_add_up_to_its_idle_time():
    want, r = _idle_ms_per_tick(SERVE)
    assert r["n_devices"] == 1 and r["ticks"] >= 6
    got = {n: spans.read_metric(n, path=SERVE) for n in SERVE_READERS}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    idle = [v for n, v in got.items() if n.startswith("idle_")]
    assert len(idle) == 5
    assert sum(idle) == pytest.approx(want, rel=0.02)
    t = spans.load(SERVE)
    steps = t.named("infer/step")
    assert len(steps) == r["ticks"]
    assert sum(s.stats["admitted"] for s in steps) == 6
    assert len(t.named("serve/fanout")) == len(steps)
    # a first token waits at least for its own delivery, at most a tick
    tails = spans.tick_tails_ms(t)
    assert len(tails) == 6
    assert 0 < min(tails) and max(tails) < max(s.dur_ps for s in steps) / 1e9
    for n in TRAIN_READERS:
        assert spans.read_metric(n, path=SERVE) is None


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_a_reader_returns_none_and_never_raises_without_its_spans(
        m, monkeypatch, tmp_path):
    """Through the harness's own door, ``read_layer_metric``, as ``run.py``
    calls it: on the trace that predates the spans (the parent's side of
    a traced run), on a file that is no trace, and with no trace at all."""
    ctx = {"facts": {}, "trace": trace.reduce_trace(OLD), "config": {},
           "traffic": {}, "device_kind": "TPU v5 lite"}
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\x0a\xff\xff\xff not a trace")
    for path in (OLD, str(junk), None):
        monkeypatch.setattr(spans, "newest_trace", lambda p=path: p)
        assert metrics.read_layer_metric(m["name"], ctx) is None


def test_unreadable_names_what_a_result_line_would_lack(monkeypatch):
    """What ``serve_cell._trace_readable`` asks of a traced piece before
    it keeps it: the serve trace gives the chat cell's span metrics, a
    train trace (no engine tick, no decode) gives none of its trace- or
    span-sourced ones, and facts are not its business."""
    chat = [m for m in common.manifest()["per_layer"]
            if "serve-gpt2-large-chat" in m.get("workloads", [])]
    traced = {m["name"] for m in chat
              if m["source"] in ("device_trace", "program_span")}
    config = common.load_json(os.path.join(
        common.BENCH_DIR, "configs", "gpt2-large.json"))
    facts = {"queue_wait_p50_ms": 1.0, "decode_context_tokens": 100.0,
             "decode_tok_per_step": 3.0}

    def lacks(path):
        monkeypatch.setattr(spans, "newest_trace", lambda: path)
        return set(metrics.unreadable(chat, {
            "facts": facts, "trace": trace.reduce_trace(path),
            "config": config, "traffic": {},
            "device_kind": "TPU v5 lite"}))

    on_serve = lacks(SERVE)
    assert not {n for n in on_serve if n.startswith(
        ("idle_", "fanout_", "tick_tail", "decode_step", "prefill_ms",
         "device_idle", "queue_wait"))}, on_serve
    # (the idle share is the one number any device trace gives)
    assert lacks(TRAIN_DP4) == traced - {"queue_wait_p50_ms.ttft",
                                         "device_idle_share.itl"}
    assert not (on_serve | lacks(TRAIN_DP4)) - traced


def test_one_parse_a_process():
    assert spans.load(TRAIN_DP4) is spans.load(TRAIN_DP4)
    assert spans.load("/no/such/file.xplane.pb") is None


def test_segments_take_the_innermost_span_across_threads():
    def sp(name, a, b, thread="t0"):
        return spans.Span(name, a, b, thread, {})
    t = spans.Trace(sorted([
        sp("infer/step", 0, 100), sp("infer/admit", 5, 20),
        sp("infer/decode", 20, 80), sp("infer/sample", 40, 80),
        sp("infer/deliver", 80, 95), sp("serve/fanout", 100, 110, "loop"),
        # the next tick runs on another executor thread; its child is
        # recorded a little past its end
        sp("infer/step", 120, 200, "t1"), sp("infer/deliver", 190, 205, "t1"),
    ], key=lambda s: (s.start_ps, -s.dur_ps)), [], [], None)
    assert spans.segments(t, spans.SERVE_PHASES) == [
        (0, 5, "deliver"), (5, 20, "admit"), (20, 40, "dispatch"),
        (40, 80, "fetch"), (80, 95, "deliver"), (95, 100, "deliver"),
        (120, 190, "deliver"), (190, 200, "deliver")]
    assert spans.segments(t, spans.TRAIN_PHASES) == []
    got = spans._overlap_by_phase([(10, 30), (90, 130)],
                                  spans.segments(t, spans.SERVE_PHASES))
    assert got == {"admit": 10, "dispatch": 10, "deliver": 20}


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  common.manifest()["workloads"]])
def test_traced_rehearsal_exits_3_with_what_a_cpu_trace_can_give(cell):
    # the seed is one at which the toy burst tail (24 requests in 12 s,
    # the gamma cycle turned by the seed) has arrivals in its first
    # seconds: a rehearsal takes one piece, and one with no arrival in it
    # has no prefill for tick_tail_ms to read
    out = subprocess.run(
        [sys.executable, os.path.join(common.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", "3000000025", "--trace", "1",
         "--rehearse-on-cpu"], cwd=common.CHECKOUT, capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    names = set(line["metric_names"])
    # host planes give durations of spans; idle time needs a device plane
    want = {m["name"] for m in NEW if cell in m["workloads"]
            and m["name"].startswith(("fanout_ms", "tick_tail_ms"))}
    assert want <= names
    assert not any(n.startswith("idle_") for n in names)
