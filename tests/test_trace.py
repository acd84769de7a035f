"""Per-request distributed tracing + flight recorder (r24).

One ``trace_id`` minted at router submission follows the request
through routing, queueing, the prefix walk, prefill, both handoff legs
(riding the ``KVHandoff`` payload across replicas), failovers and
hedge races — the span tree must be complete and gap-free in every
case.  Anomalies (injected chaos faults here) dump the ring as a
loadable Perfetto JSON.  The steady-state decode overhead of tracing
is budgeted under 1% by decomposition (the r09 telemetry pattern).
"""

import json
import time

import numpy as np
import pytest


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def tiny_f32():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig.tiny(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(autouse=True)
def _no_faults():
    from ray_tpu.util import chaos
    chaos.clear_faults()
    yield
    chaos.clear_faults()


@pytest.fixture(autouse=True)
def _fresh_trace(monkeypatch):
    """Every test starts with sample=1, a fresh ring, and no dump dir
    (tests that want a dir/rate set it and refresh themselves)."""
    from ray_tpu.telemetry import trace
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "1")
    monkeypatch.delenv("RAY_TPU_TRACE_RING", raising=False)
    monkeypatch.delenv("RAY_TPU_TRACE_DIR", raising=False)
    trace.trace_config(refresh=True)
    trace.reset()
    yield
    trace.trace_config(refresh=True)
    trace.reset()


# ride the compile caches the earlier files already paid for (the
# tier-1 budget rule — see test_disagg.py's note)
import test_inference as _ti  # noqa: E402

_EXEC_CACHE = _ti._EXEC_CACHE
_ENGINE_KW = {"slots": 2, "page_size": 16, "buckets": (16, 32, 64),
              "telemetry": False, "executable_cache": _EXEC_CACHE}


def _make_engine(tiny, **over):
    from ray_tpu.inference import InferenceEngine
    cfg, params = tiny
    kw = dict(_ENGINE_KW)
    kw.update(over)
    return InferenceEngine(cfg, params, **kw)


def _make_replica(tiny, rid, **over):
    from ray_tpu.fleet import EngineReplica
    return EngineReplica(rid, _make_engine(tiny, **over))


def _fcfg(**over):
    from ray_tpu.fleet import FleetConfig
    base = dict(retries=2, affinity=True, affinity_cap=8,
                up_depth=4.0, ttft_slo=0.0, dwell=1.0, backoff=0.0,
                backoff_max=8.0, slow_factor=0.0, hedge=False)
    base.update(over)
    return FleetConfig(**base)


def _tel():
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry
    return FleetTelemetry(config=TelemetryConfig(enabled=True))


def _prompt(n, vocab, seed=0):
    return list(np.random.RandomState(seed).randint(0, vocab, size=n))


def _assert_gap_free(trace_mod, tid):
    """One rooted, parent-complete span tree: exactly one root (the
    ``request`` span), and every other span's parent is in the same
    trace — a dangling parent means a propagation gap."""
    spans = trace_mod.spans_for(tid)
    assert spans, f"no spans recorded for trace {tid}"
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s.get("parent_id") is None]
    assert [r["name"] for r in roots] == ["request"]
    dangling = [(s["name"], s["parent_id"]) for s in spans
                if s.get("parent_id") is not None
                and s["parent_id"] not in ids]
    assert not dangling, f"spans with missing parents: {dangling}"
    return spans


# ------------------------------------------------------------ propagation
def test_disagg_handoff_one_trace_gap_free(tiny_f32):
    """A disagg request is ONE trace: the context rides the prefill
    submit and then the handoff payload, so prefill-side and
    decode-side spans join the same gap-free tree — with both transfer
    legs and the importer's install visible."""
    from ray_tpu.fleet import DisaggRouter
    from ray_tpu.telemetry import trace
    cfg, _ = tiny_f32
    prompt = _prompt(36, cfg.vocab_size, seed=1)
    router = DisaggRouter([_make_replica(tiny_f32, "tp0")],
                          [_make_replica(tiny_f32, "td0")],
                          cfg=_fcfg(), rng_seed=0, telemetry=_tel())
    s = router.remote({"tokens": prompt, "max_new_tokens": 4})
    assert len(s.result()) == 4 and s.error is None
    spans = _assert_gap_free(trace, s.trace.trace_id)
    names = {x["name"] for x in spans}
    assert {"request", "route", "queue", "prefix_walk", "prefill",
            "handoff.export", "handoff.import", "handoff.install",
            "first_token", "request_end"} <= names
    replicas = {(x.get("attributes") or {}).get("replica")
                for x in spans} - {None}
    assert {"tp0", "td0"} <= replicas       # the tree spans BOTH sides
    # the decode ticks carry the trace id in the coalesced global span
    ticks = [x for x in trace.recorder().spans()
             if x["name"] == "decode_tick"]
    assert any(s.trace.trace_id in (t["attributes"]["trace_ids"])
               for t in ticks)
    assert router.quiesce() and router.leak_free()


def test_death_failover_single_trace(tiny_f32):
    """A mid-stream replica death re-routes the stream; the second
    attempt's route/queue/prefill spans land in the SAME trace with a
    cause-tagged ``failover`` event, and the failover counter ticks."""
    from ray_tpu.fleet import FleetRouter
    from ray_tpu.telemetry import trace
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    prompts = [_prompt(20 + 3 * i, cfg.vocab_size, seed=30 + i)
               for i in range(4)]
    ref = _make_replica(tiny_f32, "df-ref")
    expected = ref.engine.generate(prompts, max_new_tokens=4)
    tel = _tel()
    reps = [_make_replica(tiny_f32, f"df{i}") for i in range(3)]
    router = FleetRouter(reps, cfg=_fcfg(), rng_seed=0, telemetry=tel)
    chaos.install_faults("serve.replica@2")
    streams = [router.remote({"tokens": p, "max_new_tokens": 4})
               for p in prompts]
    outs = [list(s) for s in streams]
    chaos.clear_faults()
    for out, want in zip(outs, expected):
        assert out == want
    failed_over = [s for s in streams if s.retries > 0]
    assert failed_over
    assert tel.summary()["failovers"].get("dead", 0) >= 1
    for s in failed_over:
        spans = _assert_gap_free(trace, s.trace.trace_id)
        routes = [x for x in spans if x["name"] == "route"]
        assert len(routes) >= 2             # original pick + re-route
        evs = [x for x in spans if x["name"] == "failover"]
        assert evs and all(
            x["attributes"]["cause"] == "dead" for x in evs)
        # the re-route landed somewhere else than the corpse
        assert (routes[-1]["attributes"]["picked"]
                != routes[0]["attributes"]["picked"])
    while any(r.alive and r.engine.has_work() for r in reps):
        router.poll()
    assert router.leak_free()


def test_hedge_won_single_trace(tiny_f32):
    """A won hedge race is one trace: ``hedge_issued`` and
    ``hedge_resolved(winner=hedge)`` events join the stream's tree,
    and the ``serve_hedges_won_total{winner=hedge}`` counter ticks."""
    from ray_tpu.fleet import FleetRouter
    from ray_tpu.telemetry import trace
    cfg, _ = tiny_f32
    prompt = _prompt(8, cfg.vocab_size, seed=40)
    ref = _make_replica(tiny_f32, "hw-ref")
    (expected,) = ref.engine.generate([prompt], max_new_tokens=4)
    reps = [_make_replica(tiny_f32, "hw0"),
            _make_replica(tiny_f32, "hw1")]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(affinity=False, hedge=True,
                                         hedge_min=0.05),
                         rng_seed=2, telemetry=tel)
    s = router.remote({"tokens": prompt, "max_new_tokens": 4})
    primary = router._replicas[s.replica_id]
    hedge_rep = next(r for r in reps if r.id != primary.id)
    s.submitted_ts -= 10.0                 # force the hedge deadline
    router._maybe_hedge()
    assert s.hedge_replica_id == hedge_rep.id
    for ev in hedge_rep.step():            # hedge leg wins the race
        router._dispatch(hedge_rep, ev)
    deadline = time.monotonic() + 5
    while not s.done and time.monotonic() < deadline:
        router.poll()
    assert list(s.generated) == expected and s.error is None
    assert tel.summary()["hedge_winners"] == {"hedge": 1}
    spans = _assert_gap_free(trace, s.trace.trace_id)
    issued = [x for x in spans if x["name"] == "hedge_issued"]
    resolved = [x for x in spans if x["name"] == "hedge_resolved"]
    assert len(issued) == 1 and len(resolved) == 1
    assert issued[0]["attributes"]["hedge_replica"] == hedge_rep.id
    assert resolved[0]["attributes"]["winner"] == "hedge"
    while any(r.has_work() for r in reps):
        router.poll()
    assert all(r.leak_free() for r in reps)


def test_hedge_winner_label_validated():
    tel = _tel()
    tel.record_hedge_won("primary")
    tel.record_hedge_won("hedge")
    tel.record_hedge_won("hedge")
    assert tel.summary()["hedge_winners"] == {"primary": 1, "hedge": 2}
    with pytest.raises(ValueError):
        tel.record_hedge_won("bystander")


# ----------------------------------------------------------- flight dumps
def test_injected_handoff_fault_dumps_perfetto(tiny_f32, tmp_path,
                                               monkeypatch):
    """An injected ``serve.handoff`` fault dumps the ring to
    ``RAY_TPU_TRACE_DIR`` as a loadable Perfetto chrome-trace JSON
    whose events include the faulted request's rooted spans and pids
    from both pools — the self-contained post-mortem."""
    from ray_tpu.fleet import DisaggRouter
    from ray_tpu.telemetry import trace
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    monkeypatch.setenv("RAY_TPU_TRACE_DIR", str(tmp_path))
    trace.trace_config(refresh=True)
    trace.reset()
    prompts = [_prompt(20 + 3 * i, cfg.vocab_size, seed=50 + i)
               for i in range(2)]
    router = DisaggRouter(
        [_make_replica(tiny_f32, "fp0")],
        [_make_replica(tiny_f32, "fd0"),
         _make_replica(tiny_f32, "fd1")],
        cfg=_fcfg(), rng_seed=0, telemetry=_tel())
    # hits 1+2 are the first stream's export+import legs; hit 3 faults
    # the second stream's export — by then the ring holds a complete
    # cross-replica story
    plan = chaos.install_faults("serve.handoff@3")
    streams = [router.remote({"tokens": p, "max_new_tokens": 4})
               for p in prompts]
    outs = [list(s) for s in streams]
    chaos.clear_faults()
    assert len(plan.fired) == 1
    assert all(len(o) == 4 for o in outs)
    faulted = [s for s in streams if s.retries > 0]
    assert len(faulted) == 1
    dumps = sorted(tmp_path.glob("flight-injected_fault-*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["metadata"]["trigger"] == "injected_fault"
    events = doc["traceEvents"]
    assert events == sorted(events, key=lambda e: e["ts"])
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert by_name["anomaly/injected_fault"][0]["args"]["site"] \
        == "serve.handoff"
    # the dump spans both pools (prefill pid + a decode-side span)
    pids = {e["pid"] for e in events}
    assert "fp0" in pids and ({"fd0", "fd1"} & pids)
    # the faulted request's tree is rooted in the dump
    tid = faulted[0].trace.trace_id
    mine = [e for e in events if e["args"].get("trace_id") == tid]
    assert any(e["name"] == "request" for e in mine)
    assert any(e["name"] == "route" for e in mine)
    assert router.quiesce() and router.leak_free()


def test_unsampled_records_nothing_anomaly_still_lands(tiny_f32,
                                                       monkeypatch):
    """sample=0: requests mint unsampled, the ring stays empty through
    a full serve (the hot-path guard), but an anomaly trigger still
    records — the trigger itself must never be invisible."""
    from ray_tpu.fleet import FleetRouter
    from ray_tpu.telemetry import trace
    cfg, _ = tiny_f32
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "0")
    trace.trace_config(refresh=True)
    trace.reset()
    router = FleetRouter([_make_replica(tiny_f32, "u0")],
                         cfg=_fcfg(), rng_seed=0, telemetry=_tel())
    s = router.remote({"tokens": _prompt(12, cfg.vocab_size),
                       "max_new_tokens": 3})
    assert len(s.result()) == 3
    assert s.trace.sampled is False
    assert len(trace.recorder()) == 0
    trace.anomaly("wedge", replica="u0")
    assert len(trace.recorder()) == 1


def test_trace_env_knobs(monkeypatch):
    from ray_tpu.telemetry import trace
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "0.25")
    monkeypatch.setenv("RAY_TPU_TRACE_RING", "128")
    cfg = trace.trace_config(refresh=True)
    assert cfg.sample == 0.25 and cfg.ring == 128 and cfg.dir is None
    trace.reset()
    assert trace.recorder().capacity == 128
    # deterministic head sampling: every 4th mint samples at 0.25
    verdicts = [trace.mint().sampled for _ in range(8)]
    assert sum(verdicts) == 2
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "junk")
    monkeypatch.setenv("RAY_TPU_TRACE_RING", "-5")
    cfg = trace.trace_config(refresh=True)
    assert cfg.sample == 1.0 and cfg.ring == 4096
    monkeypatch.setenv("RAY_TPU_TRACE_SAMPLE", "7")
    assert trace.trace_config(refresh=True).sample == 1.0


def test_ring_is_bounded_and_counts_drops(monkeypatch):
    from ray_tpu.telemetry import trace
    monkeypatch.setenv("RAY_TPU_TRACE_RING", "8")
    trace.trace_config(refresh=True)
    trace.reset()
    ctx = trace.mint(sampled=True)
    for i in range(20):
        trace.record_span(f"s{i}", ctx, start=float(i), dur=0.0)
    rec = trace.recorder()
    assert len(rec) == 8 and rec.recorded == 20 and rec.dropped == 12
    assert [r["name"] for r in rec.spans()] == \
        [f"s{i}" for i in range(12, 20)]


def test_deadline_expiry_records_anomaly(tiny_f32):
    """A blown TTFT deadline fires the ``deadline`` anomaly trigger
    with the budget kind attributed (regression: the trigger's attrs
    must not collide with ``anomaly()``'s own signature)."""
    from ray_tpu.inference import DeadlineExceededError
    from ray_tpu.telemetry import trace
    cfg, _ = tiny_f32
    eng = _make_engine(tiny_f32, slots=1)
    eng.submit(_prompt(8, cfg.vocab_size), max_new_tokens=4)
    r2 = eng.submit(_prompt(8, cfg.vocab_size, seed=1),
                    max_new_tokens=4, ttft_deadline_s=1e-4)
    time.sleep(0.005)                      # r2 queued behind r1's slot
    errs = {}
    while eng.has_work():
        for ev in eng.step():
            rid, _tok, _done = ev
            if ev.error is not None:
                errs[rid] = ev.error
    assert isinstance(errs[r2], DeadlineExceededError)
    anomalies = [r for r in trace.recorder().spans()
                 if r["name"] == "anomaly/deadline"]
    assert anomalies and anomalies[0]["attributes"]["budget"] == "ttft"
    assert eng.leak_free()


# ---------------------------------------------------------------- overhead
def test_trace_overhead_under_one_percent(tiny_f32):
    """Budget: traced steady-state decode exceeds untraced by <1%.

    Checked by decomposition (the r09 telemetry precedent — a direct
    A/B cannot resolve 1% against CI step variance): (1) the absolute
    per-tick tracing cost, measured over many iterations of the exact
    per-tick work ``_decode`` adds (the sampled-trace scan plus ONE
    coalesced ``decode_tick`` record); (2) the real engine's
    steady-state decode step wall; assert (1) < 1% of (2)."""
    from ray_tpu.telemetry import trace
    cfg, _ = tiny_f32

    # (2) the real decode step's steady wall (median), on the shared
    # pre-compiled executables — mirrors the engine the fleet runs
    eng = _make_engine(tiny_f32)
    for p in ([1, 2, 3], [4, 5, 6]):
        eng.submit(_prompt(12, cfg.vocab_size, seed=sum(p)),
                   max_new_tokens=24)
    walls = []
    while eng.has_work():
        t0 = time.monotonic()
        eng.step()
        walls.append(time.monotonic() - t0)
    walls = sorted(walls[2:])              # drop the prefill ticks
    steady = walls[len(walls) // 2]

    # (1) per-tick tracing cost: the sampled scan + one global span
    class _Req:
        def __init__(self, ctx):
            self.trace = ctx

    active = [_Req(trace.mint(sampled=True).child("s1"))
              for _ in range(2)]
    tick_t0 = time.monotonic()
    # best-of-batches: the MIN per-tick cost is the honest per-call
    # price — a mean is polluted by scheduler preemption from sibling
    # test processes, which is load on the box, not tracing overhead
    per_tick = float("inf")
    for _ in range(5):
        n = 500
        t0 = time.monotonic()
        for _ in range(n):
            traced = [r.trace.trace_id for r in active
                      if r.trace is not None and r.trace.sampled]
            if traced:
                trace.record_span("decode_tick", None,
                                  start=trace.epoch_of(tick_t0),
                                  dur=0.001, active=len(active),
                                  trace_ids=traced, replica="r0")
        per_tick = min(per_tick, (time.monotonic() - t0) / n)

    overhead = per_tick / steady
    assert overhead < 0.01, (
        f"per-tick tracing cost {per_tick * 1e6:.1f}µs is "
        f"{overhead:.2%} of the {steady * 1e3:.2f}ms steady decode "
        "step — exceeds the 1% budget")
