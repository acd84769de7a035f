"""The start-up timeline the program keeps itself: ``setup/*`` spans
(and ``infer/compile``) kept by ``util/tracing.py`` whatever the tracing
flag says, jax's own trace / lower / load / compile time spans put
beside them by ``_private/compile_cache.py``, one JSON line a record in
``<session_dir>/logs/startup_<pid>.jsonl``, and
``util.state.startup_timeline()`` over a session's files."""

import json
import os
import subprocess
import sys
import time

import pytest

from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_record():
    """Each test starts with an empty start-up record and no session."""
    tracing.clear_recorded(startup=True)
    before = tracing._session_dir
    tracing._session_dir = None
    yield
    tracing._session_dir = before
    tracing.clear_recorded(startup=True)


def _names(records):
    return [r["name"] for r in records]


def _file_records(session_dir, pid=None):
    path = os.path.join(session_dir, "logs",
                        f"startup_{pid or os.getpid()}.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_a_setup_span_is_kept_without_tracing_and_no_other_is():
    assert not tracing.is_enabled()
    with tracing.span("infer/step", tick=1):
        pass
    with tracing.span("setup/probe", n=1) as sp:
        sp.set(m=2)
        assert sp.recording
    with tracing.span("infer/compile", kind="decode", bucket=0):
        pass
    kept = tracing.recorded_spans()
    assert _names(kept) == ["setup/probe", "infer/compile"]
    rec = kept[0]
    assert rec["attributes"] == {"n": 1, "m": 2}
    assert rec["pid"] == os.getpid() and rec["role"] == "driver"
    assert rec["dur"] == sp.dur and rec["dur"] >= 0
    assert rec["end"] == pytest.approx(rec["start"] + rec["dur"])
    assert abs(rec["start"] - time.time()) < 60 and "tid" in rec
    assert tracing.kept_stats() == {"kept": 2, "dropped": 0,
                                    "cap": tracing._MAX_KEPT}


def test_the_cap_counts_what_it_drops_and_no_trim_takes_a_kept_record(
        monkeypatch):
    monkeypatch.setattr(tracing, "_MAX_KEPT", 3)
    monkeypatch.setattr(tracing, "_MAX_RECORDS", 4)
    monkeypatch.setattr(tracing, "_TRIM_EVERY", 2)
    for i in range(5):
        with tracing.span("setup/probe", i=i):
            pass
    assert tracing.kept_stats() == {"kept": 3, "dropped": 2, "cap": 3}
    tracing.enable_tracing()
    try:
        for i in range(20):
            with tracing.span("loose", i=i):
                pass
    finally:
        tracing.disable_tracing()
    spans = tracing.recorded_spans()
    # the first three kept, in place; of the others the newest, never
    # more than the limit and one trim's batch
    assert [r["attributes"]["i"] for r in spans[:3]] == [0, 1, 2]
    loose = [r["attributes"]["i"] for r in spans if r["name"] == "loose"]
    assert loose == list(range(20 - len(loose), 20))
    assert 4 <= len(loose) < 4 + 2
    tracing.clear_recorded()
    assert _names(tracing.recorded_spans()) == ["setup/probe"] * 3
    tracing.clear_recorded(startup=True)
    assert tracing.recorded_spans() == []
    assert tracing.kept_stats()["dropped"] == 0


def test_records_wait_for_the_session_directory_then_go_line_by_line(
        tmp_path):
    os.makedirs(tmp_path / "logs")
    with tracing.span("setup/early"):
        pass
    tracing.keep("jax/trace", 12.5, 0.25, fun_name="f")
    assert not os.listdir(tmp_path / "logs")
    tracing.use_session_dir(str(tmp_path))
    assert tracing.session_dir() == str(tmp_path)
    assert _names(_file_records(tmp_path)) == ["setup/early", "jax/trace"]
    with tracing.span("setup/late"):
        pass
    lines = _file_records(tmp_path)
    assert lines == json.loads(json.dumps(tracing.recorded_spans()))
    assert lines[1]["start"] == 12.5 and lines[1]["dur"] == 0.25
    assert lines[1]["attributes"] == {"fun_name": "f"}
    # a directory that has gone loses the file, never the process
    tracing.use_session_dir(str(tmp_path / "gone"))
    with tracing.span("setup/after"):
        pass
    assert _names(tracing.recorded_spans())[-1] == "setup/after"


@pytest.fixture
def one_chip_actor():
    """A real session with one made-up chip and one actor that holds it;
    yields after ``shutdown()``."""
    import ray_tpu

    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def pid(self):
                return os.getpid()

        holder = Holder.remote()
        worker_pid = ray_tpu.get(holder.pid.remote(), timeout=120)
        from ray_tpu.util import state
        live = state.startup_timeline()
        driver_events = json.loads(ray_tpu.timeline())
    finally:
        ray_tpu.shutdown()
    return {"worker_pid": worker_pid, "live": live,
            "driver_events": driver_events}


def test_a_session_leaves_its_processes_records_in_order(one_chip_actor):
    from ray_tpu.util import state
    after = state.startup_timeline()           # after shutdown()
    assert _names(after)[:len(one_chip_actor["live"])] == \
        _names(one_chip_actor["live"])
    assert [r["start"] for r in after] == sorted(r["start"] for r in after)
    by_name = {}
    for r in after:
        by_name.setdefault(r["name"], []).append(r)
    init, = by_name["setup/init"]
    assert init["role"] == "driver" and init["pid"] == os.getpid()
    assert init["attributes"] == {"chips": 1}
    parts = [r for r in after if r["name"].startswith("setup/init/")]
    assert {r["name"].rpartition("/")[2] for r in parts} >= {
        "session", "control_plane", "object_store", "node_manager",
        "core_worker"}
    # the first child holds the session directory's own making: the
    # record of it was held and written once the directory was there
    assert all(init["start"] <= r["start"]
               and r["end"] <= init["end"] + 1e-3 for r in parts)
    pid = one_chip_actor["worker_pid"]
    spawn = next(r for r in by_name["setup/worker_spawn"]
                 if r["attributes"]["tpu"] == 1)
    boot = next(r for r in by_name["setup/worker_boot"] if r["pid"] == pid)
    actor = next(r for r in by_name["setup/actor_init"] if r["pid"] == pid)
    first = next(r for r in by_name["setup/task"] if r["pid"] == pid)
    assert spawn["role"] == "driver" and spawn["attributes"]["forked"] == 0
    assert boot["role"] == actor["role"] == "worker"
    assert boot["attributes"]["worker"] == spawn["attributes"]["worker"]
    assert actor["attributes"] == {"cls": "Holder", "chips": 1}
    assert first["attributes"] == {"fn": "actor.pid", "chips": 1}
    assert (init["end"] <= spawn["start"] <= boot["start"]
            <= boot["end"] <= actor["start"] <= actor["end"]
            <= first["start"])
    # the process's own start lies before its main's first line, by
    # less than the interpreter could ever take
    assert 0 < boot["start"] - boot["attributes"]["exec_epoch"] < 60
    # the worker's file, line for line, and the driver's own records in
    # ray_tpu.timeline() while the session lived
    on_disk = _file_records(tracing.session_dir(), pid)
    assert on_disk == [{k: v for k, v in r.items()}
                       for r in after if r["pid"] == pid]
    shown = {e["name"] for e in one_chip_actor["driver_events"]}
    assert {"setup/init", "setup/worker_spawn"} <= shown
    assert "setup/actor_init" not in shown      # another process's


def test_the_log_view_lists_and_serves_the_start_up_files():
    import ray_tpu
    from ray_tpu._private.worker import global_node

    ray_tpu.init(num_cpus=1)
    try:
        nm = global_node().node_manager
        name = f"startup_{os.getpid()}.jsonl"
        assert name in [f["name"] for f in nm.list_logs()]
        tail = nm.tail_log(name).decode().splitlines()
        assert json.loads(tail[0])["name"].startswith("setup/init")
    finally:
        ray_tpu.shutdown()
    assert os.path.exists(os.path.join(tracing.session_dir(), "logs", name))


@pytest.fixture
def every_jax_span(monkeypatch):
    """jax's time spans listened to, none too short to keep."""
    from ray_tpu._private import compile_cache
    monkeypatch.setattr(compile_cache, "_MIN_RECORD_S", 0.0)
    compile_cache.enable_compile_cache()
    return compile_cache


def test_a_fresh_jit_leaves_its_trace_lowering_and_compile(every_jax_span):
    import jax
    import jax.numpy as jnp

    before = every_jax_span.compile_stats()

    @jax.jit
    def startup_probe(x):
        return jnp.tanh(x) @ x

    startup_probe(jnp.ones((8, 8))).block_until_ready()
    mine = [r for r in tracing.recorded_spans()
            if "startup_probe" in r["attributes"].get("fun_name", "")]
    assert _names(mine) == ["jax/trace", "jax/lower", "jax/compile"]
    assert [r["attributes"]["fun_name"] for r in mine] == [
        "startup_probe", "jit(startup_probe)", "jit(startup_probe)"]
    assert all(r["dur"] > 0 and abs(r["start"] - time.time()) < 60
               for r in mine)
    assert mine[0]["end"] <= mine[1]["start"] + 1e-3
    after = every_jax_span.compile_stats()
    assert set(after) == {"compiles", "compile_seconds", "cache_hits",
                          "cache_misses", "trace_seconds", "lower_seconds",
                          "load_seconds"}
    assert after["compiles"] > before["compiles"]
    for key in ("compile_seconds", "trace_seconds", "lower_seconds"):
        assert after[key] > before[key]
    assert after["load_seconds"] == before["load_seconds"]   # no cache


def test_a_trace_inside_a_trace_is_summed_once(every_jax_span):
    cc = every_jax_span
    trace = "/jax/core/compile/jaxpr_trace_duration"
    before = cc.compile_stats()["trace_seconds"]
    t = time.time()     # behind every trace this thread has made
    cc._on_time_span(trace, t + 1.0, t + 1.5, fun_name="inner_a")
    cc._on_time_span(trace, t + 2.0, t + 2.25, fun_name="inner_b")
    cc._on_time_span(trace, t, t + 3.0, fun_name="outer")
    cc._on_time_span(trace, t + 4.0, t + 4.0005, fun_name="later")
    del cc._OPEN.traces[-2:]        # made up: nothing real lies inside
    assert cc.compile_stats()["trace_seconds"] - before == \
        pytest.approx(3.0005)
    kept = [r["attributes"]["fun_name"] for r in tracing.recorded_spans()
            if r["name"] == "jax/trace"]
    assert kept == ["inner_a", "inner_b", "outer", "later"]
    cc._MIN_RECORD_S = 1e-3        # the fixture's patch puts it back
    cc._on_time_span(trace, t - 9.0, t - 8.9995, fun_name="too_short")
    cc._OPEN.traces.pop()
    assert "too_short" not in [r["attributes"]["fun_name"]
                               for r in tracing.recorded_spans()]


_SECOND_PROCESS = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu._private import compile_cache
from ray_tpu.util import tracing
compile_cache._MIN_RECORD_S = 0.0
compile_cache.enable_compile_cache()

@jax.jit
def cached_probe(x):
    return jnp.tanh(x) @ x

cached_probe(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"records": [
    r for r in tracing.recorded_spans()
    if "cached_probe" in r["attributes"].get("fun_name", "")],
    "stats": compile_cache.compile_stats()}))
"""


def test_a_second_process_loads_what_the_first_compiled(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _SECOND_PROCESS, str(tmp_path)],
            env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert _names(first["records"]) == ["jax/trace", "jax/lower",
                                        "jax/compile"]
    assert _names(second["records"]) == ["jax/trace", "jax/lower",
                                         "jax/load"]
    load = second["records"][2]
    assert load["attributes"]["fun_name"] == "jit(cached_probe)"
    assert 0 < load["attributes"]["retrieval_s"] <= load["dur"]
    assert first["stats"]["load_seconds"] == 0.0
    assert second["stats"]["load_seconds"] >= load["dur"]
    assert second["stats"]["cache_hits"] >= 1


def test_a_train_steps_first_call_and_an_engines_misses_leave_records():
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig, init_params
    from ray_tpu.parallel.mesh import make_mesh

    cfg = GPTConfig.tiny(dtype=jnp.float32)
    fns = training.build_gpt_train(
        cfg, make_mesh(dp=1, devices=jax.devices()[:1]), telemetry=True)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    batch = training.synthetic_lm_batch(jax.random.PRNGKey(1), 2, 32,
                                        cfg.vocab_size)
    for _ in range(2):
        state, _ = fns["step_fn"](state, batch)
    tel = fns["telemetry"]
    kept = tracing.recorded_spans()
    weights, = [r for r in kept if r["name"] == "setup/weights"]
    n_leaves = len(jax.tree.leaves(state))
    assert weights["attributes"]["leaves"] == n_leaves
    assert weights["attributes"]["bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(state))
    first, = [r for r in kept if r["name"] == "setup/first_step"]
    assert first["attributes"] == {"label": tel.label}
    assert first["start"] == tel.records[0]["ts"]
    assert first["dur"] == tel.records[0]["wall_s"] == tel.first_step_s

    engine = InferenceEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                             slots=2, page_size=16, buckets=(16, 32))
    engine.generate([[1, 2, 3, 4, 5]], max_new_tokens=3)
    engine.generate([[5, 4, 3, 2, 1]], max_new_tokens=3)    # all hits
    kept = tracing.recorded_spans()
    built, = [r for r in kept if r["name"] == "setup/engine"]
    assert built["attributes"] == {"slots": 2, "pages": engine.cache.num_pages,
                                   "buckets": 2}
    misses = [r["attributes"] for r in kept if r["name"] == "infer/compile"]
    assert misses == [{"kind": "prefill", "bucket": 16},
                      {"kind": "decode", "bucket": 0}]
    assert sum(engine.compile_counts.values()) == len(misses)
    # not one span of a tick, a token or a later step among them
    assert {r["name"].split("/")[0] for r in kept} <= {"setup", "infer",
                                                       "jax"}
    assert [r["name"] for r in kept if r["name"].startswith("infer/")] == \
        ["infer/compile"] * 2


def test_the_kept_records_reach_the_one_exporter():
    from ray_tpu.telemetry import chrome_trace

    with tracing.span("setup/probe", chips=1):
        time.sleep(0.002)
    tracing.keep("jax/compile", time.time(), 0.5, fun_name="jit(step)")
    events = {e["name"]: e for e in chrome_trace.trace_events(
        include_steps=False, include_requests=False)}
    assert events["setup/probe"]["args"] == {"chips": 1}
    assert events["setup/probe"]["dur"] >= 2000
    assert events["jax/compile"]["dur"] == pytest.approx(0.5e6)
    assert events["jax/compile"]["args"] == {"fun_name": "jit(step)"}
    assert all(e["ph"] == "X" and e["pid"] == "host"
               for e in events.values())
