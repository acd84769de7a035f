"""The set-up readers (``reduce/startup.py`` and the thirteen
``layer_metrics/setup_*`` files) on two timelines recorded on a v5e: a
run of ``train-gpt2-124m-b24x1024`` and one of ``serve-gpt2-large-chat``
(``tests/data/startup_*_v5e.json``: the run's ``startup_timeline()``,
its two facts, and the values its own result line printed)."""

import copy
import json
import os

import pytest

from benchmark.harness import common, metrics
from benchmark.reduce import startup

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SHARED = ["setup_runtime_up_s", "setup_worker_place_s", "setup_to_devices_s",
          "setup_worker_boot_s", "setup_trace_s", "setup_lower_s",
          "setup_load_s", "setup_compile_s", "setup_executables",
          "setup_weights_s"]
OWN = {"train": ["setup_first_step_s"],
       "serve": ["setup_engine_build_s", "setup_engine_compile_s"]}


@pytest.fixture(params=["train", "serve"])
def run(request, monkeypatch):
    rec = common.load_json(os.path.join(
        DATA, f"startup_{request.param}_v5e.json"))
    rec["kind"] = request.param
    # what ``run.py`` finds after its shutdown: the session's timeline
    monkeypatch.setattr(startup, "_TIMELINE", rec["records"])
    return rec


def test_every_reader_reads_what_the_run_printed(run):
    assert run["device"]["platform"] == "tpu"
    ctx = {"facts": run["facts"]}
    names = SHARED + OWN[run["kind"]]
    got = {n: metrics.read_layer_metric(n, ctx) for n in names}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got == {n: pytest.approx(run["metrics"][n]) for n in names}
    # the other kind's own parts are not in this timeline
    other = OWN["serve" if run["kind"] == "train" else "train"]
    assert [metrics.read_layer_metric(n, ctx) for n in other] == \
        [None] * len(other)


def test_the_three_parts_add_up_to_worker_ready_s(run):
    got = startup.parts(run["records"], run["facts"])
    assert (got["setup_runtime_up_s"] + got["setup_worker_place_s"]
            + got["setup_to_devices_s"]) == pytest.approx(
                run["facts"]["worker_ready_s"], abs=1e-6)
    assert min(got[n] for n in SHARED) >= 0.0
    assert got["setup_worker_boot_s"] < got["setup_worker_place_s"] + 5
    # a warm run: the persistent cache answered for every executable
    # over its floor of one second of compiling, and what the compiler
    # still built, in this process as in every other, lies under it
    mine = [r for r in run["records"] if r["pid"] == _chip_pid(run)
            and r["start"] < _cut(run)]
    built = [r["dur"] for r in mine if r["name"] == "jax/compile"]
    loaded = [r for r in mine if r["name"] == "jax/load"]
    assert max(built) < 1.0
    assert got["setup_compile_s"] == pytest.approx(sum(built))
    assert got["setup_load_s"] == pytest.approx(
        sum(r["dur"] for r in loaded))
    assert got["setup_load_s"] > 1.0
    assert all(0 < r["attributes"]["retrieval_s"] <= r["dur"]
               for r in loaded)
    assert got["setup_executables"] == len(built) + len(loaded)


def _cut(run):
    init = next(r for r in run["records"] if r["name"] == "setup/init")
    return init["start"] + run["facts"]["setup_s"]


def _chip_pid(run):
    return next(r["pid"] for r in run["records"]
                if r["name"] in ("setup/actor_init", "setup/task")
                and r["attributes"].get("chips", 0) > 0)


def test_nothing_behind_the_cut_or_of_another_process_is_read(run):
    facts = run["facts"]
    want = startup.parts(run["records"], facts)
    pid, cut = _chip_pid(run), _cut(run)
    late = [{"name": name, "start": cut + 1.0, "dur": 2.0, "end": cut + 3.0,
             "tid": 1, "pid": pid, "role": "worker",
             "attributes": {"fun_name": "late", "kind": "decode"}}
            for name in ("jax/trace", "jax/lower", "jax/load",
                         "jax/compile", "infer/compile")]
    elsewhere = [dict(r, pid=pid + 1, start=cut - 5.0) for r in late]
    assert startup.parts(run["records"] + late + elsewhere, facts) == want
    # a shorter set-up cuts earlier: the sums can only shrink
    half = (facts["worker_ready_s"] + facts["setup_s"]) / 2
    early = startup.parts(run["records"], dict(facts, setup_s=half))
    for name in ("setup_trace_s", "setup_lower_s", "setup_load_s",
                 "setup_executables"):
        assert early[name] < want[name]
    for name in ("setup_runtime_up_s", "setup_worker_place_s",
                 "setup_to_devices_s", "setup_worker_boot_s"):
        assert early[name] == want[name]


def test_a_trace_inside_a_trace_counts_once(run):
    pid, cut = _chip_pid(run), _cut(run)
    base = startup.parts(run["records"], run["facts"])["setup_trace_s"]
    outer = {"name": "jax/trace", "start": cut - 3.0, "dur": 1.0,
             "tid": 7, "pid": pid, "attributes": {"fun_name": "outer"}}
    inner = dict(outer, start=cut - 2.75, dur=0.5,
                 attributes={"fun_name": "inner"})
    beside = dict(inner, tid=8)      # another thread's is its own
    got = startup.parts(run["records"] + [outer, inner, beside],
                        run["facts"])["setup_trace_s"]
    assert got == pytest.approx(base + 1.0 + 0.5)


def test_what_is_missing_reads_nothing_and_never_raises(run, monkeypatch):
    facts, records = run["facts"], run["records"]
    names = SHARED + OWN[run["kind"]]
    # a program with no start-up timeline (the parent of PR 63)
    from ray_tpu.util import state
    monkeypatch.setattr(startup, "_TIMELINE", None)
    monkeypatch.delattr(state, "startup_timeline")
    assert [metrics.read_layer_metric(n, {"facts": facts})
            for n in names] == [None] * len(names)
    assert startup.timeline() is None
    # no fact, no chip worker, no ``setup/init``, a broken record
    no_chips = copy.deepcopy(records)
    for r in no_chips:
        r["attributes"].pop("chips", None)
    for recs, fcts, left in (
            (records, {}, {"setup_runtime_up_s"}),
            (no_chips, facts, {"setup_runtime_up_s"}),
            ([r for r in records if r["name"] != "setup/init"], facts,
             set()),
            ([{"name": "setup/init"}], facts, set())):
        got = {n: startup.read_metric(n, {"facts": fcts}, recs)
               for n in names}
        assert {n for n, v in got.items() if v is not None} == left
    without_ready = startup.parts(records, {"setup_s": facts["setup_s"]})
    assert "setup_to_devices_s" not in without_ready
    assert "setup_worker_place_s" in without_ready


def test_the_manifest_lists_them_as_counters_under_setup_s():
    listed = {m["name"]: m for m in common.manifest()["per_layer"]
              if m["name"].startswith("setup_")}
    assert sorted(listed) == sorted(SHARED + OWN["train"] + OWN["serve"])
    cells = {w["name"] for w in common.manifest()["workloads"]}
    for name, m in listed.items():
        # ``harness/metrics.unreadable`` throws a traced piece away for a
        # span- or trace-sourced metric its reader cannot read, which is
        # what these read on a program without the record
        assert m["source"] == "program_counter" and m["moves"] == "setup_s"
        assert m["better"] == "lower"
        assert m["unit"] == ("count" if name == "setup_executables"
                             else "s")
        what = common.load_json(metrics.reader_file(name) + ".json")["what"]
        assert "startup_timeline" in what and "startup_<pid>.jsonl" in what
    for kind, prefix in (("train", "train-"), ("serve", "serve-")):
        for name in OWN[kind]:
            assert set(listed[name]["workloads"]) == {
                c for c in cells if c.startswith(prefix)}
    assert all("workloads" not in listed[n] for n in SHARED)
    assert {listed[n]["layer"] for n in SHARED} == {"runtime"}
