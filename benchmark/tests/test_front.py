"""The serve front's readers (``reduce/front.py``): the split of the
device's idle time between two engine ticks on a trace built in memory,
on the recorded one-chip serve trace, whose program opens no
``serve/emit`` and whose fan-outs carry no ``more`` (the parent's side of
a traced run), and on traces without an engine tick."""

import os

import pytest

from benchmark.harness import common, metrics
from benchmark.reduce import front, spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SERVE = os.path.join(DATA, "toy_serve_v5e.xplane.pb")
TRAIN_DP4 = os.path.join(DATA, "toy_train_dp4_v5e.xplane.pb")
OLD = os.path.join(DATA, "toy_train_v5e.xplane.pb")
SPAN_READERS = list(front.IDLE_READERS) + ["emit_ms_per_tok",
                                           "pump_wait_ms_per_tick"]
FACT_READERS = ["decode_ahead_share", "decode_write_pages_per_row"]
US = 1_000_000                  # the planted times are microseconds


def _planted(devices):
    """Three ticks on an executor thread, the pump's fan-outs and the
    streams' emits on the loop's: tick 1's tokens go out while tick 2
    runs (two emits lie between the ticks in part, one inside tick 2),
    after tick 2 the pump finds no work, two more tokens go out, and the
    replica waits for tick 3."""
    def sp(name, a, b, thread, **stats):
        return spans.Span(name, a * US, b * US, thread, stats)
    mine = [
        sp("infer/step", 1010, 1990, "exec"),
        sp("infer/decode", 1100, 1500, "exec"),
        sp("serve/fanout", 2300, 2400, "loop", more=1),
        sp("serve/emit", 2410, 2440, "loop", rid=0),
        sp("serve/emit", 2440, 2700, "loop", rid=1),
        sp("infer/step", 2460, 3490, "exec"),
        sp("serve/emit", 2700, 2900, "loop", rid=2),
        sp("serve/fanout", 3600, 3650, "loop", more=0),
        sp("serve/emit", 3660, 3760, "loop", rid=0),
        sp("serve/emit", 3800, 3850, "loop", rid=1),
        sp("infer/step", 5010, 5990, "exec"),
        sp("serve/fanout", 6100, 6200, "loop", more=1),
    ]
    ticks = [sp(trace.TICK, a, b, "exec")
             for a, b in ((1000, 2000), (2450, 3500), (5000, 6000))]
    return spans.Trace(sorted(mine, key=lambda s: (s.start_ps, -s.dur_ps)),
                       ticks, [[(a * US, b * US) for a, b in busy]
                               for busy in devices], None)


BUSY = [(1200, 1900), (2420, 2450), (2500, 3400), (3700, 3720),
        (5100, 5900)]


@pytest.mark.parametrize("devices", [[BUSY], [BUSY, []],
                                     [BUSY, [(900, 7000)]]],
                         ids=["one_chip", "one_idle_beside_it",
                              "one_busy_beside_it"])
def test_the_three_parts_add_up_to_between_ticks_to_the_picosecond(
        devices, monkeypatch):
    t = _planted(devices)
    got = front.split(t)
    ps = got["ps"]
    assert ps["emit"] + ps["no_work"] + ps["other"] == ps["between_ticks"]
    assert min(ps.values()) >= 0 and got["ticks"] == 3
    whole = spans.idle_by_phase(t, spans.SERVE_PHASES)
    assert ps["between_ticks"] / len(devices) / 1e12 == \
        whole["seconds"]["between_ticks"]
    # and through the readers, as a result line prints them
    path = os.path.abspath("planted.xplane.pb")
    monkeypatch.setitem(spans._CACHE, path, t)
    parts = [front.read_metric(n, path) for n in front.IDLE_READERS]
    assert sum(parts) == pytest.approx(spans.read_metric(
        "idle_between_ticks_ms_per_tick", path), rel=1e-12)


def test_the_planted_trace_reads_what_was_planted(monkeypatch):
    t = _planted([BUSY])
    # between ticks the device idles 10 + 430 + 10 + 210 + 1290 + 10 us;
    # 10 + 10 + 40 + 40 + 50 of them under an emit; after the fan-out
    # with more=0, under no emit and up to tick 3: 10 + 40 + 1160
    assert front.split(t)["ps"] == {
        "emit": 150 * US, "no_work": 1210 * US, "other": 600 * US,
        "between_ticks": 1960 * US}
    path = os.path.abspath("planted.xplane.pb")
    monkeypatch.setitem(spans._CACHE, path, t)
    assert front.read_metric("idle_emit_ms_per_tick", path) == \
        pytest.approx(0.150 / 3)
    assert front.read_metric("idle_no_work_ms_per_tick", path) == \
        pytest.approx(1.210 / 3)
    assert front.read_metric("idle_front_other_ms_per_tick", path) == \
        pytest.approx(0.600 / 3)
    # emits of 30, 260, 200, 100, 50 us start in the window
    assert front.read_metric("emit_ms_per_tok", path) == pytest.approx(0.1)
    # the fan-outs in the window start 310 and 110 us after a tick's end;
    # the third starts past the last tick's end, outside the window
    assert front.read_metric("pump_wait_ms_per_tick", path) == \
        pytest.approx(0.21)


def test_an_emit_counts_before_no_work_and_only_between_ticks():
    # the emit inside tick 2 and the busy device take nothing from it
    t = _planted([[(900, 7000)]])
    assert set(front.split(t)["ps"].values()) == {0}
    # without the fan-out's word everything that is no emit is "other"
    t = _planted([BUSY])
    for s in t.named("serve/fanout"):
        s.stats.pop("more")
    assert front.split(t)["ps"] == {
        "emit": 150 * US, "no_work": 0, "other": 1810 * US,
        "between_ticks": 1960 * US}


def _ctx(path):
    return {"facts": {}, "trace": trace.reduce_trace(path), "config": {},
            "traffic": {}, "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("suffix", [".itl", ".batch"])
def test_a_program_without_the_new_spans_reads_zeros_and_the_whole(
        suffix, monkeypatch):
    """The parent's side of a traced run, through the harness's own door:
    ticks and a device plane, no ``serve/emit``, no ``more``."""
    monkeypatch.setattr(spans, "newest_trace", lambda: SERVE)
    got = {n: metrics.read_layer_metric(n + suffix, _ctx(SERVE))
           for n in SPAN_READERS + ["idle_between_ticks_ms_per_tick"]}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["idle_emit_ms_per_tick"] == 0.0
    assert got["idle_no_work_ms_per_tick"] == 0.0
    assert got["emit_ms_per_tok"] == 0.0
    assert got["idle_front_other_ms_per_tick"] == \
        got["idle_between_ticks_ms_per_tick"] > 0
    # a fan-out follows its tick within the time between two ticks
    steps = spans.load(SERVE).named("infer/step")
    gaps = [(b.start_ps - a.end_ps) / 1e9 for a, b in zip(steps, steps[1:])]
    assert 0 < got["pump_wait_ms_per_tick"] < max(gaps)
    assert not set(metrics.unreadable(
        [m for m in common.manifest()["per_layer"]
         if os.path.basename(metrics.reader_file(m["name"]))
         in SPAN_READERS], _ctx(SERVE)))


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_trace_without_an_engine_tick_reads_none_and_never_raises(
        name, monkeypatch, tmp_path):
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\x0a\xff\xff\xff not a trace")
    for path in (OLD, TRAIN_DP4, str(junk), None):
        monkeypatch.setattr(spans, "newest_trace", lambda p=path: p)
        ctx = _ctx(path) if path in (OLD, TRAIN_DP4) else \
            {"facts": {}, "trace": None}
        for suffix in (".itl", ".batch"):
            assert metrics.read_layer_metric(name + suffix, ctx) is None
    assert front.split(None) is None
    assert front.emit_ms(None) is None and front.pump_wait_ms(None) is None
    # ticks of the harness and device planes, and no infer/step
    assert front.split(spans.load(TRAIN_DP4)) is None


@pytest.mark.parametrize("name,facts,want", [
    ("decode_ahead_share", {"telemetry.decode.dispatches_ahead": 450,
                            "telemetry.decode.dispatches": 600}, 75.0),
    ("decode_ahead_share", {"telemetry.decode.dispatches": 600}, None),
    ("decode_ahead_share", {"telemetry.decode.dispatches_ahead": 0,
                            "telemetry.decode.dispatches": 0}, None),
    ("decode_write_pages_per_row",
     {"telemetry.decode.tail_pages_rewritten": 640,
      "telemetry.decode.rows_written": 640}, 1.0),
    ("decode_write_pages_per_row",
     {"telemetry.decode.tail_pages_rewritten": 64 * 500,
      "telemetry.decode.rows_written": 680}, 64 * 500 / 680),
    ("decode_write_pages_per_row", {}, None),
])
def test_the_counters_reach_a_metric_as_two_counts(name, facts, want):
    for suffix in (".itl", ".batch"):
        assert metrics.read_layer_metric(name + suffix,
                                         {"facts": facts}) == want


def test_every_new_entry_stands_where_the_time_between_ticks_stands():
    per_layer = {m["name"]: m for m in common.manifest()["per_layer"]}
    new = [m for m in per_layer.values()
           if os.path.basename(metrics.reader_file(m["name"]))
           in SPAN_READERS + FACT_READERS]
    assert len(new) == 14
    for m in new:
        suffix = m["name"].rpartition(".")[2]
        model = per_layer["idle_between_ticks_ms_per_tick." + suffix]
        assert (m["moves"], m["workloads"]) == (model["moves"],
                                                model["workloads"])
        stem = os.path.basename(metrics.reader_file(m["name"]))
        if stem in SPAN_READERS:
            assert (m["source"], m["layer"], m["better"], m["unit"]) == (
                "program_span", "serve front", "lower", "ms")
            assert os.path.exists(metrics.reader_file(m["name"]) + ".py")
        else:
            assert m["source"] == "program_counter"
