"""The program's one span primitive, ``ray_tpu.util.tracing.span``, on
the profiler's clock: which spans the engine tick, the serve pump and
the wrapped train step open, how they nest, what they carry, and that
they cost nothing a compiled function or an unprofiled run can see."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)        # benchmark/ is read-only tooling here

ENGINE_SPANS = ("infer/step", "infer/admit", "infer/prefill",
                "infer/prefill_cached", "infer/decode", "infer/sample",
                "infer/deliver", "infer/compile")
NEW_NAMES = ENGINE_SPANS + ("serve/fanout", "serve/emit", "more",
                            "loss_read", "train/record", "train/dispatch",
                            "train/sync")


def _host_threads(path):
    """{thread name: [Event]} of the host planes, python frames left out."""
    from benchmark.reduce.xplane import read_xplane
    out = {}
    for plane in read_xplane(
            path, want_lines=lambda n: n.startswith("/host:")):
        for index, line in enumerate(plane.lines):
            evs = [e for e in line.events if not e.name.startswith("$")]
            if evs:     # two threads may share a name
                out[f"{plane.name}/{line.name}#{index}"] = evs
    return out


def _tiny_engine():
    import jax
    import jax.numpy as jnp

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, InferenceEngine(cfg, params, slots=2, page_size=16,
                                buckets=(16, 32, 64), telemetry=True)


def _drive(engine, vocab, sampled_ctx=None):
    """Two requests that share their first page (the second prefills
    from the cache), stepped to the end: {rid: tokens}."""
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, vocab, size=16))
    first = engine.submit(shared + list(rng.randint(0, vocab, size=9)),
                          max_new_tokens=4, trace_ctx=sampled_ctx)
    out = {first: []}
    ticks = 0
    second = None
    while engine.has_work():
        for ev in engine.step():
            out.setdefault(ev[0], []).append(int(ev[1]))
        ticks += 1
        if ticks == 2:
            second = engine.submit(
                shared + list(rng.randint(0, vocab, size=5)),
                max_new_tokens=3)
            out[second] = []
    return out, first, second


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """One profiled run of the tiny engine: its xplane, the tokens it
    produced, and the sampled request's trace id."""
    import jax

    from benchmark.reduce.xplane import find_xplane
    from ray_tpu.telemetry import trace as trace_mod
    cfg, engine = _tiny_engine()
    ctx = trace_mod.mint(sampled=True)
    logdir = str(tmp_path_factory.mktemp("engine_trace"))
    jax.profiler.start_trace(logdir)
    try:
        tokens, first, second = _drive(engine, cfg.vocab_size, ctx)
    finally:
        jax.profiler.stop_trace()
    return {"path": find_xplane(logdir), "tokens": tokens, "first": first,
            "second": second, "trace_id": ctx.trace_id, "cfg": cfg}


def test_engine_tick_opens_every_span_nested_on_one_thread(engine_trace):
    threads = _host_threads(engine_trace["path"])
    mine = [evs for evs in threads.values()
            if any(e.name == "infer/step" for e in evs)]
    assert len(mine) == 1, "the test drives the engine from one thread"
    spans = [e for e in mine[0] if e.name.startswith("infer/")]
    names = {e.name for e in spans}
    assert names == set(ENGINE_SPANS)
    steps = [e for e in spans if e.name == "infer/step"]
    # properly nested: every other span lies inside exactly one tick,
    # and two spans of one thread either nest or do not touch
    for e in spans:
        if e.name != "infer/step":
            assert sum(s.start_ps <= e.start_ps and e.end_ps <= s.end_ps
                       for s in steps) == 1, e.name
    order = sorted(spans, key=lambda e: (e.start_ps, -e.dur_ps))
    stack = []
    for e in order:
        while stack and stack[-1].end_ps <= e.start_ps:
            stack.pop()
        if stack:
            assert e.end_ps <= stack[-1].end_ps, (stack[-1].name, e.name)
        stack.append(e)

    def parents(name):
        out = set()
        for e in spans:
            if e.name == name:
                inside = [p for p in spans if p is not e
                          and p.start_ps <= e.start_ps
                          and e.end_ps <= p.end_ps]
                out.add(min(inside, key=lambda p: p.dur_ps).name)
        return out

    assert parents("infer/admit") == {"infer/step"}
    assert parents("infer/deliver") == {"infer/step"}
    # a fetch is no part of a dispatch: the tick fetches what was
    # dispatched before its own decode, after dispatching that decode
    assert parents("infer/sample") == {"infer/step"}
    decodes = [e for e in spans if e.name == "infer/decode"]
    assert {d.stats["ahead"] for d in decodes} == {1}
    for step, nxt in zip(steps, steps[1:]):
        inside = [e for e in spans if e.name in ("infer/decode",
                                                 "infer/sample")
                  and step.start_ps <= e.start_ps < step.end_ps]
        names_in = [e.name for e in sorted(inside,
                                           key=lambda e: e.start_ps)]
        if "infer/decode" in names_in:
            # nothing is fetched before the tick's decode is dispatched
            assert names_in[0] == "infer/decode", names_in
    assert parents("infer/compile") <= {"infer/prefill", "infer/decode",
                                        "infer/prefill_cached"}


def test_engine_spans_carry_counts_and_the_trace_id(engine_trace):
    (evs,) = [evs for evs in _host_threads(engine_trace["path"]).values()
              if any(e.name == "infer/step" for e in evs)]
    by = {}
    for e in evs:
        by.setdefault(e.name, []).append(e)
    steps = by["infer/step"]
    assert [s.stats["tick"] for s in steps] == list(range(len(steps)))
    n_tokens = sum(len(t) for t in engine_trace["tokens"].values())
    assert sum(s.stats["events"] for s in steps) == n_tokens
    assert sum(s.stats["admitted"] for s in steps) == 2
    assert all("active" in s.stats for s in steps)
    # counts attached on exit reach the trace like those given at entry
    assert sum(d.stats["events"] for d in by["infer/deliver"]) == n_tokens
    assert sum(d.stats["done"] for d in by["infer/deliver"]) == 2
    assert all("waiting" in a.stats for a in by["infer/admit"])
    assert sorted(a.stats["hit_pages"] for a in by["infer/admit"]
                  if "hit_pages" in a.stats) == [0, 1]
    (cold,) = by["infer/prefill"]
    (warm,) = by["infer/prefill_cached"]
    assert cold.stats["rid"] == engine_trace["first"]
    assert cold.stats["bucket"] == 32 and cold.stats["cached"] == 0
    assert warm.stats["cached"] == 16 and warm.stats["bucket"] == 16
    # the sampled request's spans share its id with the flight recorder
    assert cold.stats["trace_id"] == engine_trace["trace_id"]
    assert "trace_id" not in warm.stats
    from ray_tpu.telemetry import trace as trace_mod
    ring = [s for s in trace_mod.spans_for(engine_trace["trace_id"])
            if s["name"] == "prefill"]
    # the ring's prefill: its dispatch and the wait for its token, the
    # first fetch after it on the thread
    fetch = min((s for s in by["infer/sample"]
                 if s.start_ps >= cold.end_ps), key=lambda s: s.start_ps)
    assert fetch.stats["rows"] == 1
    assert ring and ring[0]["dur"] == pytest.approx(
        (cold.dur_ps + fetch.dur_ps) / 1e12, rel=0.2, abs=2e-3)
    assert {d.stats["active"] for d in by["infer/decode"]} <= {1, 2}
    assert {s.stats["rows"] for s in by["infer/sample"]} == {1, 2}
    kinds = {(c.stats["kind"], c.stats["bucket"])
             for c in by["infer/compile"]}
    assert kinds == {("prefill", 32), ("prefill_cached", 16),
                     ("decode", 0)}


def test_wrapped_train_step_spans_sit_inside_the_step_annotation(tmp_path):
    import jax
    import jax.numpy as jnp

    from benchmark.reduce.xplane import find_xplane
    from ray_tpu.telemetry import StepTelemetry

    @jax.jit
    def step(state, batch):
        loss = jnp.mean((batch["tokens"] * state) ** 2)
        return state - 0.1 * loss, {"loss": loss}

    tel = StepTelemetry(label="train")
    wrapped = tel.wrap(step)
    state = jnp.float32(1.0)
    batch = {"tokens": jnp.ones((2, 8), jnp.float32)}
    state, _ = wrapped(state, batch)            # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            state, _ = wrapped(state, batch)
    finally:
        jax.profiler.stop_trace()
    (evs,) = [evs for evs in _host_threads(
        find_xplane(str(tmp_path))).values()
        if any(e.name == "train" for e in evs)]
    steps = [e for e in evs if e.name == "train"]
    assert [s.stats["step_num"] for s in steps] == [1, 2, 3]
    phases = ("train/dispatch", "train/sync", "train/loss_read",
              "train/record")
    for s in steps:
        inside = [e for e in evs if e.name in phases
                  and s.start_ps <= e.start_ps and e.end_ps <= s.end_ps]
        assert [e.name for e in sorted(inside, key=lambda e: e.start_ps)
                ] == list(phases)
        assert {e.stats["step"] for e in inside} == {s.stats["step_num"]}
    # no second span of the step's own extent
    assert not any(e.name == "train/step" for e in evs)
    # the record's times are the spans' own
    rec = tel.records[-1]
    assert rec["wall_s"] == pytest.approx(rec["dispatch_s"] + rec["sync_s"])
    assert rec["loss"] == pytest.approx(float(_["loss"]))


def _stream_two(dep):
    """Two streams through the deployment's own pump, consumed with
    asyncio: ([tokens of the first, of the second], their rids)."""
    import asyncio
    rids = []
    submit = dep.engine.submit

    def recording_submit(*args, **kwargs):
        rids.append(submit(*args, **kwargs))
        return rids[-1]

    dep.engine.submit = recording_submit

    async def consume(request):
        return [token async for token in dep(request)]

    async def run():
        rng = np.random.RandomState(11)
        vocab = dep.cfg.vocab_size
        return await asyncio.gather(
            consume({"tokens": list(rng.randint(0, vocab, size=20)),
                     "max_new_tokens": 5}),
            consume({"tokens": list(rng.randint(0, vocab, size=9)),
                     "max_new_tokens": 3}))

    tokens = asyncio.run(asyncio.wait_for(run(), timeout=300))
    return tokens, rids


def _tiny_deployment():
    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import GPTDeployment
    return GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config={"slots": 2, "page_size": 16,
                       "buckets": (16, 32, 64), "telemetry": True})


@pytest.fixture(scope="module")
def serve_trace(tmp_path_factory):
    """One profiled run of a tiny ``GPTDeployment`` in process: the host
    threads of its xplane, the two streams' tokens and their rids."""
    import jax

    from benchmark.reduce.xplane import find_xplane
    dep = _tiny_deployment()
    logdir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(logdir)
    try:
        tokens, rids = _stream_two(dep)
    finally:
        jax.profiler.stop_trace()
    path = find_xplane(logdir)
    threads = _host_threads(path)

    def named(*names):
        return sorted((e for evs in threads.values() for e in evs
                       if e.name in names), key=lambda e: e.start_ps)

    return {"path": path, "threads": threads, "named": named,
            "tokens": dict(zip(rids, tokens)), "rids": rids}


def _one_emit_a_delivered_token(t):
    emits = t["named"]("serve/emit")
    assert len(emits) == sum(len(v) for v in t["tokens"].values()) == 8
    for rid, tokens in t["tokens"].items():
        assert sum(e.stats["rid"] == rid for e in emits) == len(tokens)


def _emits_are_on_the_event_loops_thread(t):
    def holding(name):
        return {thread for thread, evs in t["threads"].items()
                if any(e.name == name for e in evs)}
    (loop,) = holding("serve/emit")
    # the pump's own span is the loop's too; the ticks run on the executor
    assert holding("serve/fanout") == {loop}
    assert holding("infer/step") and loop not in holding("infer/step")


def _an_emit_carries_the_rid_of_its_requests_prefill(t):
    prefills = t["named"]("infer/prefill", "infer/prefill_cached")
    assert sorted(p.stats["rid"] for p in prefills) == sorted(t["rids"])
    assert {e.stats["rid"] for e in t["named"]("serve/emit")} == \
        set(t["rids"])


def _the_last_emit_of_a_stream_is_done(t):
    for rid, tokens in t["tokens"].items():
        done = [e.stats["done"] for e in t["named"]("serve/emit")
                if e.stats["rid"] == rid]
        assert done == [0] * (len(tokens) - 1) + [1]


def _every_fanout_says_whether_the_pump_goes_on(t):
    fans = t["named"]("serve/fanout")
    steps = t["named"]("infer/step")
    assert len(fans) == len(steps) >= 5
    assert [f.stats["more"] for f in fans][-1] == 0
    for f in fans:
        # more=1: another tick starts, on the executor, before the pump
        # opens its next fan-out; more=0: none until a request comes
        after = [s for s in steps if s.start_ps >= f.end_ps]
        assert f.stats["more"] == int(bool(after)), f.stats
    assert sum(f.stats["events"] for f in fans) == 8


def _no_emit_is_open_across_anothers_start(t):
    emits = t["named"]("serve/emit")
    for a, b in zip(emits, emits[1:]):
        assert a.end_ps <= b.start_ps
    assert all(e.dur_ps > 0 for e in emits)


def _the_front_readers_find_the_spans_and_read_nothing_without_ticks(t):
    from benchmark.reduce import front, spans
    trace = spans.load(t["path"])
    assert len(trace.named("serve/emit")) == 8
    assert [f.stats["more"] for f in trace.named("serve/fanout")][-1] == 0
    # no tick of the harness and no device plane here: nothing to divide
    # by, and no reader raises
    assert front.split(trace) is None
    for name in (*front.IDLE_READERS, "emit_ms_per_tok",
                 "pump_wait_ms_per_tick"):
        assert front.read_metric(name, path=t["path"]) is None


@pytest.mark.parametrize("holds", [
    _one_emit_a_delivered_token, _emits_are_on_the_event_loops_thread,
    _an_emit_carries_the_rid_of_its_requests_prefill,
    _the_last_emit_of_a_stream_is_done,
    _every_fanout_says_whether_the_pump_goes_on,
    _no_emit_is_open_across_anothers_start,
    _the_front_readers_find_the_spans_and_read_nothing_without_ticks,
], ids=lambda f: f.__name__.strip("_"))
def test_serve_front_spans(serve_trace, holds):
    holds(serve_trace)


def test_span_off_records_nothing_and_changes_no_token(engine_trace,
                                                       serve_trace):
    from ray_tpu.util import tracing

    def recorded():
        # all but the start-up record (``setup/*``, ``infer/compile``,
        # jax's own time spans), which is kept whatever the flag says
        # and holds no span of a tick or a token
        spans = tracing.recorded_spans()
        assert all(r["name"].startswith(("setup/", "jax/"))
                   or r["name"] == "infer/compile"
                   for r in spans if "pid" in r)
        return [r for r in spans if "pid" not in r]

    assert not tracing.is_enabled()
    tracing.clear_recorded()
    cfg, engine = _tiny_engine()
    tokens, first, second = _drive(engine, cfg.vocab_size)
    assert recorded() == []
    # the serve front's spans too: with no profile and tracing off the
    # deployment streams the same tokens and nothing is kept
    streamed, rids = _stream_two(_tiny_deployment())
    assert recorded() == []
    assert streamed == [serve_trace["tokens"][r]
                        for r in serve_trace["rids"]]
    assert [len(s) for s in streamed] == [5, 3]
    with tracing.span("off", n=1) as sp:
        pass
    assert sp.dur is not None and sp.dur >= 0 and sp.end >= sp.start
    assert recorded() == []
    profiled = engine_trace["tokens"]
    assert tokens[first] == profiled[engine_trace["first"]]
    assert tokens[second] == profiled[engine_trace["second"]]
    assert len(tokens[first]) == 4 and len(tokens[second]) == 3


def test_span_never_imports_jax():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "from ray_tpu.util.profiling import annotate\n"
        "assert annotate is tracing.span\n"
        "assert 'jax' not in sys.modules\n"
        "with tracing.span('a', n=1) as sp:\n"
        "    sp.set(m=2)\n"
        "tracing.enable_tracing()\n"
        "with tracing.span('b', n=1) as sp:\n"
        "    sp.set(m=2)\n"
        "rec, = tracing.recorded_spans()\n"
        "assert rec['attributes'] == {'n': 1, 'm': 2}, rec\n"
        "assert rec['dur'] == sp.dur\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_no_span_name_reaches_a_compiled_function():
    """Spans are host-side context managers outside ``jit``: the HLO of
    the train step and of the engine's decode is what it was."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.parallel.mesh import make_mesh
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    mesh = make_mesh(devices=jax.devices()[:1], dp=-1)
    fns = training.build_gpt_train(cfg, mesh)
    state = fns["init_fn"](jax.random.PRNGKey(0))
    tokens = np.zeros((2, 32), np.int32)
    batch = jax.device_put({"tokens": tokens, "targets": tokens},
                           fns["batch_sharding"])
    step = getattr(fns["step_fn"], "__wrapped__", fns["step_fn"])
    texts = [step.lower(state, batch).as_text()]

    _, engine = _tiny_engine()
    sched = engine.scheduler
    args = (engine.params, *engine.cache.state,
            np.zeros((engine.slots,), np.int32), sched.lengths,
            sched.page_table)
    texts.append(engine._build_step("decode").lower(*args).as_text())
    for text in texts:
        assert len(text) > 1000
        for name in NEW_NAMES:
            assert name not in text, name


def test_spans_reader_without_device_planes_gives_none(engine_trace):
    """A CPU trace has host planes only: the spans are found, and every
    function that needs a device's idle time returns None and does not
    raise."""
    from benchmark.reduce import spans
    got = spans.load(engine_trace["path"])
    assert got is not None and got.device_busy == []
    assert len(got.named("infer/step")) >= 4
    assert spans.idle_by_phase(got, spans.SERVE_PHASES) is None
    assert spans.idle_by_phase(got, spans.TRAIN_PHASES) is None
    for name in spans.IDLE_READERS:
        assert spans.read_metric(name, path=engine_trace["path"]) is None
    assert spans.read_metric("no_such_metric",
                             path=engine_trace["path"]) is None
    assert spans.read_metric("idle_sync_ms_per_step",
                             path="/nonexistent.xplane.pb") is None
