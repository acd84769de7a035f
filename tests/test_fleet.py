"""Fleet-layer tests: pow-2/affinity routing, mid-stream failover
under deterministic chaos, the reconciler state machine (table-driven
with an explicit clock), drain-based scale-down, and the idle-stream
reaper."""

import time
import types

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


# fleet replicas share one executable cache (same geometry -> same AOT
# executables; the scale-up/restart zero-recompile claim rides on it).
# It is test_inference.py's cache: both files use the identical
# (GPTConfig.tiny f32, slots 2, page 16, buckets (16,32,64)) geometry,
# so sharing pays the tiny-engine compile once per tier-1 process
# instead of twice — the budget is the scarcest resource.  (Safe under
# the tier-1 invocation: xdist and random ordering are disabled.)
import test_inference as _ti  # noqa: E402

_EXEC_CACHE = _ti._EXEC_CACHE
_ENGINE_KW = {"slots": 2, "page_size": 16, "buckets": (16, 32, 64),
              "telemetry": False, "executable_cache": _EXEC_CACHE}


def _make_replica(tiny, rid, *, watchdog_s=0.0, **over):
    from ray_tpu.fleet import EngineReplica
    from ray_tpu.inference import InferenceEngine
    cfg, params = tiny
    kw = dict(_ENGINE_KW)
    kw.update(over)
    return EngineReplica(rid, InferenceEngine(cfg, params, **kw),
                         watchdog_s=watchdog_s)


def _fcfg(**over):
    from ray_tpu.fleet import FleetConfig
    base = dict(retries=2, affinity=True, affinity_cap=8,
                up_depth=4.0, ttft_slo=0.0, dwell=1.0, backoff=1.0,
                backoff_max=8.0)
    base.update(over)
    return FleetConfig(**base)


def _tel():
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.fleet import FleetTelemetry
    return FleetTelemetry(config=TelemetryConfig(enabled=True))


def _prompt(n, vocab, seed=0):
    return list(np.random.RandomState(seed).randint(0, vocab, size=n))


class StubReplica:
    """Router/reconciler-protocol stub: no engine, pure host state."""

    def __init__(self, rid, *, depth=0, digest=(), page_size=16):
        self.id = rid
        self.alive = True
        self.draining = False
        self.reaped = False
        self.wedges = 0
        self._depth = depth
        self._digest = frozenset(digest)
        self._drained = False
        self._next_rid = 0
        self.submit_error = None       # raised once per set
        self.submitted = 0
        self.latency = 0.0             # r19 health score (0 = unmeasured)
        self.engine = types.SimpleNamespace(
            page_size=page_size, buckets=(64,),
            cancel=lambda rid: None)

    def submit(self, prompt, **kw):
        if self.submit_error is not None:
            err, self.submit_error = self.submit_error, None
            raise err
        self.submitted += 1
        self._depth += 1
        self._next_rid += 1
        return self._next_rid

    def step(self):
        return []

    @property
    def wedged(self):
        return self.wedges > 0

    def check(self, now=None):
        pass

    def has_work(self):
        return False

    def queue_depth(self):
        return self._depth

    def waiting_depth(self):
        return self._depth

    def latency_score(self):
        return self.latency

    def prefix_digest(self):
        return self._digest

    def drain(self):
        self.draining = True

    @property
    def drained(self):
        return self.draining and self._drained

    def reap(self):
        self.reaped = True
        return 0

    def leak_free(self):
        return True


# ------------------------------------------------------------ pick logic
def test_router_pow2_converges_to_least_loaded():
    """Power-of-two-choices with depth feedback balances an initially
    skewed fleet: after routing a burst, queue depths converge (and
    the deepest replica receives the fewest assignments)."""
    from ray_tpu.fleet import FleetRouter
    reps = [StubReplica("r0", depth=12), StubReplica("r1", depth=0),
            StubReplica("r2", depth=6)]
    router = FleetRouter(reps, cfg=_fcfg(affinity=False), rng_seed=7,
                         telemetry=_tel())
    for i in range(30):
        s = router.remote({"tokens": [1, 2, 3], "max_new_tokens": 2})
        assert s.error is None and s.replica_id is not None
    depths = [r.queue_depth() for r in reps]
    # started 12 apart; pow-2 sampling converges to within a few
    assert max(depths) - min(depths) <= 4, depths
    # assignments ranked inversely to the starting depths: the
    # shallowest starter absorbed the most, the deepest the least
    assert reps[1].submitted > reps[2].submitted > reps[0].submitted


def test_router_affinity_overrides_only_healthy_under_cap():
    """Affinity routes a prompt to the replica whose digest holds its
    chained page hashes — unless that replica is over the cap or not
    healthy, where routing falls back to pow-2 / another replica."""
    from ray_tpu.fleet import FleetRouter
    from ray_tpu.inference import PrefixIndex
    prompt = _prompt(40, 512, seed=3)         # 2 hit-eligible pages @16
    h1 = PrefixIndex.chain(PrefixIndex.ROOT, prompt[:16])
    h2 = PrefixIndex.chain(h1, prompt[16:32])
    cold = StubReplica("cold", depth=0)
    warm = StubReplica("warm", depth=3, digest=(h1, h2))
    tel = _tel()
    router = FleetRouter([cold, warm], cfg=_fcfg(affinity_cap=5),
                         rng_seed=0, telemetry=tel)
    s = router.remote({"tokens": prompt, "max_new_tokens": 2})
    assert s.replica_id == "warm"             # hit wins despite depth
    assert tel.affinity_routed == 1
    # over the cap: the hit replica is hot -> pow-2 (cold is shallower)
    warm._depth = 6
    s = router.remote({"tokens": prompt, "max_new_tokens": 2})
    assert s.replica_id == "cold"
    # draining hit replica is not a candidate at all
    warm._depth = 0
    warm.draining = True
    s = router.remote({"tokens": prompt, "max_new_tokens": 2})
    assert s.replica_id == "cold"
    warm.draining = False
    # affinity off: the digest is ignored entirely
    router_off = FleetRouter([cold, warm], cfg=_fcfg(affinity=False),
                             rng_seed=0, telemetry=_tel())
    router_off.remote({"tokens": prompt, "max_new_tokens": 2})
    assert router_off.telemetry.affinity_decisions == 0
    # a short prompt (no full hit-eligible page) can't affinity-route
    s = router.remote({"tokens": prompt[:8], "max_new_tokens": 2})
    assert tel.summary()["affinity_decisions"] >= 4


def test_router_reroute_signals_and_exhaustion():
    """Draining/queue-full submit rejections re-route immediately
    (counted by cause); when every replica rejects, the stream carries
    a typed ReplicaUnavailableError — never a hang."""
    from ray_tpu.fleet import FleetRouter, ReplicaUnavailableError
    from ray_tpu.inference import QueueFullError
    from ray_tpu.inference.serve_gpt import ReplicaDrainingError
    # r0 is strictly shallower, so pow-2 picks it first — and it
    # rejects as draining (it began draining between the health check
    # and the submit): the router re-routes to r1 in the same call
    r0, r1 = StubReplica("r0", depth=0), StubReplica("r1", depth=5)
    tel = _tel()
    router = FleetRouter([r0, r1], cfg=_fcfg(affinity=False),
                         rng_seed=1, telemetry=tel)
    r0.submit_error = ReplicaDrainingError("draining")
    s = router.remote({"tokens": [1, 2], "max_new_tokens": 2})
    assert s.error is None and s.replica_id == "r1"
    assert tel.retries == {"draining": 1}

    # queue-full everywhere: each replica tried exactly once, then a
    # typed failure on the stream — never a hang
    def submit_full(prompt, **kw):
        raise QueueFullError("full")

    r0.submit = submit_full
    r1.submit = submit_full
    s = router.remote({"tokens": [1, 2], "max_new_tokens": 2})
    with pytest.raises(ReplicaUnavailableError, match="no healthy"):
        next(iter(s))
    assert tel.retries["queue_full"] == 2


# ---------------------------------------------------- failover (chaos)
def test_fleet_failover_mid_stream_chaos(tiny_f32):
    """THE chaos acceptance test: a deterministic plan kills one
    replica mid-traffic and a second replica wedges; every in-flight
    stream completes via failover with at-most-once delivery (greedy
    continuations equal the unfailed reference), the reconciler
    restores the target count with ZERO recompiles (shared executable
    cache), and no slot/page/prefix refcount leaks fleet-wide."""
    from ray_tpu.fleet import RUNNING, FleetRouter, Reconciler
    from ray_tpu.util import chaos
    cfg, params = tiny_f32

    # reference: what an unfailed engine generates for each prompt
    # (greedy + deterministic engine => failover continuations must
    # reproduce it exactly)
    shared = _prompt(32, cfg.vocab_size, seed=11)   # 2 full pages
    prompts = [shared + _prompt(5 + i, cfg.vocab_size, seed=20 + i)
               for i in range(6)]
    ref_rep = _make_replica(tiny_f32, "ref")
    expected = ref_rep.engine.generate(prompts, max_new_tokens=4)

    reps = [_make_replica(tiny_f32, f"r{i}", watchdog_s=0.05)
            for i in range(3)]
    fcfg = _fcfg(retries=2, dwell=0.0, backoff=0.0)
    router = FleetRouter(reps, cfg=fcfg, rng_seed=0, telemetry=_tel())
    rec = Reconciler(
        router, lambda rid: _make_replica(tiny_f32, rid,
                                          watchdog_s=0.05),
        target=3, cfg=fcfg)

    # the 3rd fleet step dies (replicas step in insertion order, so
    # the victim is deterministic for a fixed plan + trace)
    plan = chaos.install_faults("serve.replica@3")
    streams = [router.remote({"tokens": p, "max_new_tokens": 4})
               for p in prompts]
    # pump a little traffic, then wedge one surviving replica that
    # still has in-flight work (its streams must fail over too)
    for _ in range(2):
        router.poll()
    victim_dead = [r for r in reps if not r.alive]
    assert victim_dead and plan.fired == [("serve.replica", 3)]
    wedge = next(r for r in reps
                 if r.alive and r.engine.has_work())
    wedge.stall()
    outs = [list(s) for s in streams]
    chaos.clear_faults()

    # every stream completed via failover: full length, at-most-once
    # (the stream asserts over-delivery), exact greedy continuation
    for out, want in zip(outs, expected):
        assert out == want
    assert all(s.error is None and s.done for s in streams)
    assert any(s.retries > 0 for s in streams)
    # the wedge was detected by the watchdog, not deadlines
    assert wedge.wedges >= 1
    # reconcile until the fleet is back at target with all RUNNING
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        rec.reconcile()
        states = rec.states()
        if sorted(states.values()).count(RUNNING) == 3:
            break
        time.sleep(0.01)
    assert list(rec.states().values()).count(RUNNING) == 3
    assert rec.restarts_total == 2          # the corpse + the wedge
    # zero steady-state recompiles: replacements compiled NOTHING
    for r in router.replicas():
        assert r.engine.stats()["compiles"] == {
            "prefill": 0, "prefill_cached": 0, "decode": 0}
    # fleet-wide leak audit (dead replicas were reaped at failover)
    assert router.leak_free()
    for r in reps:
        assert r.leak_free()
    tel = router.telemetry.summary()
    assert tel["router_retries"]["dead"] >= 2
    assert tel["replica_restarts"] == 2


def test_failover_budget_exhausts_typed(tiny_f32):
    """With every replica dead, a mid-stream failover surfaces the
    typed ReplicaUnavailableError — the zero-hung-streams contract."""
    from ray_tpu.fleet import (FleetRouter, ReplicaUnavailableError)
    from ray_tpu.util import chaos
    reps = [_make_replica(tiny_f32, f"x{i}") for i in range(2)]
    router = FleetRouter(reps, cfg=_fcfg(retries=1), rng_seed=0,
                         telemetry=_tel())
    cfg, _ = tiny_f32
    s = router.remote({"tokens": _prompt(8, cfg.vocab_size),
                       "max_new_tokens": 4})
    # both replicas die on their next tick
    chaos.install_faults("serve.replica@1,serve.replica@2")
    with pytest.raises(ReplicaUnavailableError):
        list(s)
    chaos.clear_faults()
    assert s.done
    assert all(not r.alive for r in reps)
    assert all(r.leak_free() for r in reps)     # corpses were reaped


def test_route_site_fault_reroutes(tiny_f32):
    """An injected serve.route submit failure re-routes to another
    replica transparently; the request still completes."""
    from ray_tpu.fleet import FleetRouter
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    reps = [_make_replica(tiny_f32, f"s{i}") for i in range(2)]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(), rng_seed=0, telemetry=tel)
    plan = chaos.install_faults("serve.route@1")
    s = router.remote({"tokens": _prompt(8, cfg.vocab_size),
                       "max_new_tokens": 3})
    out = list(s)
    chaos.clear_faults()
    assert plan.fired == [("serve.route", 1)]
    assert len(out) == 3 and s.error is None
    assert tel.retries == {"dead": 1}
    assert router.leak_free()


def test_failover_past_largest_bucket_is_typed():
    """A re-prefill grown past the fleet's largest bucket fails the
    stream with a typed ReplicaUnavailableError naming the geometry
    limit — not a raw engine ValueError."""
    from ray_tpu.fleet import FleetRouter, ReplicaUnavailableError
    router = FleetRouter([StubReplica("r0"), StubReplica("r1")],
                         cfg=_fcfg(), telemetry=_tel())
    s = router.remote({"tokens": list(range(60)),
                       "max_new_tokens": 20})   # admissible: 60 <= 64
    assert s.error is None
    s.generated = list(range(10))               # 10 tokens emitted...
    router._failover(s)                         # ...then the replica dies
    assert isinstance(s.error, ReplicaUnavailableError)
    assert "largest prefill bucket" in str(s.error)
    # mixed-geometry replicas are refused up front
    with pytest.raises(ValueError, match="geometry"):
        router.add_replica(StubReplica("odd", page_size=8))


# -------------------------------------------------- drain / scale-down
def test_draining_replica_never_admits_and_drains_clean(tiny_f32):
    """DRAINING: admission raises the typed ReplicaDrainingError, the
    router routes new work elsewhere, in-flight streams finish (zero
    dropped), and the reconciler retires the replica once drained."""
    from ray_tpu.fleet import (DRAINING, FleetRouter, Reconciler,
                               RUNNING, STOPPED)
    from ray_tpu.inference.serve_gpt import ReplicaDrainingError
    cfg, _ = tiny_f32
    reps = [_make_replica(tiny_f32, f"d{i}") for i in range(2)]
    router = FleetRouter(reps, cfg=_fcfg(affinity=False), rng_seed=3,
                         telemetry=_tel())
    rec = Reconciler(router, lambda rid: None, target=1,
                     cfg=_fcfg(dwell=0.0))
    # land one stream on each replica, then drain d1 mid-flight
    streams = []
    for i in range(4):
        streams.append(router.remote(
            {"tokens": _prompt(8, cfg.vocab_size, seed=i),
             "max_new_tokens": 3}))
    target = reps[1]
    rec.instances[target.id].state = DRAINING
    target.drain()
    with pytest.raises(ReplicaDrainingError):
        target.submit([1, 2, 3], max_new_tokens=2)
    # new work only lands on the survivor
    s_new = router.remote({"tokens": _prompt(8, cfg.vocab_size,
                                             seed=9),
                           "max_new_tokens": 2})
    assert s_new.replica_id == reps[0].id
    # every in-flight stream completes (zero dropped by the drain)
    for s in streams + [s_new]:
        assert list(s) and s.error is None
    assert target.drained
    acts = rec.reconcile()
    assert f"{target.id}: DRAINING->STOPPED" in acts
    assert target.id not in rec.states()
    assert rec.states() == {reps[0].id: RUNNING}
    assert len(router.replicas()) == 1
    assert STOPPED not in rec.states().values()
    assert all(r.leak_free() for r in reps)


# ------------------------------------------------ reconciler (stubbed)
def _stub_fleet(n=2, **cfg_over):
    from ray_tpu.fleet import FleetRouter, Reconciler
    reps = [StubReplica(f"r{i}") for i in range(n)]
    fcfg = _fcfg(**cfg_over)
    router = FleetRouter(reps, cfg=fcfg, telemetry=_tel())
    made = []

    def factory(rid):
        r = StubReplica(rid)
        made.append(r)
        return r

    rec = Reconciler(router, factory, target=n, cfg=fcfg, now=0.0)
    return reps, router, rec, made


def test_reconciler_wedged_requires_watchdog_signal():
    """Table-driven core transitions: RUNNING persists without a
    health signal; WEDGED only on the watchdog counter (or death);
    restart waits out the backoff, then replaces 1:1 with escalating,
    capped backoff."""
    from ray_tpu.fleet import (RESTARTING, RUNNING, WEDGED)
    reps, router, rec, made = _stub_fleet(2, dwell=1.0, backoff=2.0,
                                          backoff_max=8.0)
    # no signal: RUNNING forever, no spawns
    for t in (1.0, 10.0, 100.0):
        assert rec.reconcile(now=t) == []
    assert set(rec.states().values()) == {RUNNING}
    # watchdog signal -> WEDGED immediately (no dwell on failures)
    reps[0].wedges = 1
    acts = rec.reconcile(now=100.5)
    assert acts == ["r0: RUNNING->WEDGED"]
    # backoff gate: restart_at = 100.5 + 2.0 (first restart)
    assert rec.reconcile(now=101.0) == []      # still backing off
    assert rec.states()["r0"] == WEDGED
    acts = rec.reconcile(now=102.6)
    assert any("RESTARTING" in a for a in acts)
    assert "r0" not in rec.states()
    assert reps[0].reaped and not reps[0].alive
    (new_id,) = [rid for rid, st in rec.states().items()
                 if st == RESTARTING]
    assert rec.restarts_total == 1
    # next pass: replacement goes RUNNING
    rec.reconcile(now=103.0)
    assert rec.states()[new_id] == RUNNING
    # the replacement crash-loops: its backoff doubled (2 -> 4)
    made[0].alive = False
    rec.reconcile(now=103.5)
    assert rec.states()[new_id] == WEDGED
    inst = rec.instances[new_id]
    assert inst.restart_at == pytest.approx(103.5 + 4.0)
    # ... and is capped at backoff_max
    assert rec._backoff(10) == 8.0


def test_reconciler_dead_replica_is_wedge_equivalent():
    from ray_tpu.fleet import WEDGED
    reps, router, rec, made = _stub_fleet(2, backoff=0.0)
    reps[1].alive = False
    acts = rec.reconcile(now=1.0)
    assert "r1: RUNNING->WEDGED" in acts
    acts = rec.reconcile(now=1.1)
    assert any("RESTARTING" in a for a in acts)
    assert rec.restarts_total == 1
    assert WEDGED not in rec.states().values()
    # the fleet is back at target; no extra restore spawn happened
    assert len(router.replicas()) == 2


def test_reconciler_scale_up_hysteresis_and_cap():
    """Sustained queue pressure scales up only after the dwell; a
    blip does not; max_replicas caps growth; consecutive scale
    actions are a dwell apart."""
    from ray_tpu.fleet import Reconciler, FleetRouter
    reps = [StubReplica("r0"), StubReplica("r1")]
    fcfg = _fcfg(up_depth=4.0, dwell=2.0)
    router = FleetRouter(reps, cfg=fcfg, telemetry=_tel())
    rec = Reconciler(router, lambda rid: StubReplica(rid), target=2,
                     max_replicas=4, cfg=fcfg, now=0.0)
    # a blip: pressure appears then clears before the dwell
    reps[0]._depth = reps[1]._depth = 10
    assert rec.reconcile(now=1.0) == []           # breach starts
    reps[0]._depth = reps[1]._depth = 0
    assert rec.reconcile(now=2.0) == []           # cleared: reset
    reps[0]._depth = reps[1]._depth = 10
    assert rec.reconcile(now=3.0) == []           # new breach window
    acts = rec.reconcile(now=5.0)                 # sustained >= dwell
    assert len([a for a in acts if "scale-up" in a]) == 1
    assert len(router.replicas()) == 3
    # still breaching: the next scale-up waits a dwell after the last
    assert all("scale-up" not in a for a in rec.reconcile(now=5.5))
    rec.reconcile(now=7.5)
    assert len(router.replicas()) == 4
    # capped at max_replicas=4: no further growth ever
    for t in (10.0, 12.0, 20.0):
        assert all("scale-up" not in a
                   for a in rec.reconcile(now=t))
    assert len(router.replicas()) == 4


def test_reconciler_dead_while_draining_is_retired_not_replaced():
    """A replica that dies (or wedges) mid-drain must not zombie in
    DRAINING forever: it is reaped and retired with NO replacement —
    it was leaving anyway (scale-down), so the target math must not
    resurrect it."""
    from ray_tpu.fleet import DRAINING
    reps, router, rec, made = _stub_fleet(3)
    rec.target = 2
    inst = rec.instances["r2"]
    inst.state = DRAINING
    reps[2].drain()
    reps[2].alive = False            # dies mid-drain: never `drained`
    acts = rec.reconcile(now=1.0)
    assert "r2: DRAINING->STOPPED" in acts
    assert reps[2].reaped
    assert "r2" not in rec.states()
    assert len(router.replicas()) == 2 and made == []


# ------------------------------------------------- gray failure (r19)
def test_router_latency_demotion_is_soft():
    """Health scoring: the latency outlier past slow_factor x the
    fleet median is demoted (excluded from routing while faster
    replicas exist), uniformly slow fleets demote NOBODY (the median
    moves with the shared cause), and an all-demoted candidate set
    still routes — demotion is never a dead-end."""
    from ray_tpu.fleet import FleetRouter
    reps = [StubReplica(f"r{i}") for i in range(3)]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(affinity=False,
                                         slow_factor=3.0),
                         rng_seed=1, telemetry=tel)
    for r, lat in zip(reps, (0.01, 0.012, 0.1)):
        r.latency = lat
    router._update_health()
    assert router.slow_replicas() == {"r2"}
    assert tel.replica_demotions == 1
    router._update_health()                  # same episode: no re-count
    assert tel.replica_demotions == 1
    # routing: the demoted replica receives nothing
    for i in range(12):
        s = router.remote({"tokens": [1, 2, 3], "max_new_tokens": 2})
        assert s.replica_id != "r2"
    # uniform slowness: median moves with it, nobody demoted
    for r in reps:
        r.latency = 0.1
    router._update_health()
    assert router.slow_replicas() == set()
    # soft demotion: even with every candidate demoted, route anyway
    router._demoted = {"r0", "r1", "r2"}
    s = router.remote({"tokens": [1, 2, 3], "max_new_tokens": 2})
    assert s.error is None and s.replica_id is not None
    # slow_factor=0 disables scoring entirely
    off = FleetRouter([StubReplica("a"), StubReplica("b")],
                      cfg=_fcfg(slow_factor=0.0), telemetry=_tel())
    off.replicas()[0].latency = 99.0
    off._update_health()
    assert off.slow_replicas() == set()


def test_router_pow2_latency_penalty():
    """The pow-2 comparison weighs queue depth by relative latency: a
    2x-median (below the demotion threshold) replica loses the pick
    at equal depth — slowness costs routing share before it costs
    membership."""
    from ray_tpu.fleet import FleetRouter
    reps = [StubReplica("fast"), StubReplica("meh")]
    router = FleetRouter(reps, cfg=_fcfg(affinity=False,
                                         slow_factor=3.0),
                         rng_seed=5, telemetry=_tel())
    reps[0].latency, reps[1].latency = 0.01, 0.02
    router._update_health()
    assert router.slow_replicas() == set()   # 2x < slow_factor 3x
    # at equal depth the fast replica wins the pick outright ...
    assert router._effective_load(reps[0]) < \
        router._effective_load(reps[1])
    s = router.remote({"tokens": [1, 2], "max_new_tokens": 1})
    assert s.replica_id == "fast"
    # ... and across a burst (depth feedback included: the slow
    # replica still gets work once the fast one is 2x deeper —
    # penalty, not starvation) the fast replica carries more
    for _ in range(19):
        router.remote({"tokens": [1, 2], "max_new_tokens": 1})
    assert reps[0].submitted > reps[1].submitted


def test_hedge_deadline_and_capacity_gate():
    """The hedge deadline floors at hedge_min until enough TTFT
    samples exist, then tracks hedge_factor x rolling p99; and a
    hedge is only issued when the best alternative has spare capacity
    NOW (empty waiting queue) — a saturated fleet never hedges itself
    deeper into saturation."""
    from ray_tpu.fleet import FleetRouter
    reps = [StubReplica("h0"), StubReplica("h1")]
    router = FleetRouter(reps, cfg=_fcfg(affinity=False, hedge=True,
                                         hedge_factor=2.0,
                                         hedge_min=0.05),
                         rng_seed=0, telemetry=_tel())
    assert router.hedge_deadline_s() == pytest.approx(0.05)
    for _ in range(20):
        router._record_ttft(0.1)
    assert router.hedge_deadline_s() == pytest.approx(0.2)
    router._record_ttft(1.0)                 # a tail sample moves p99
    assert router.hedge_deadline_s() == pytest.approx(2.0)
    # capacity gate: the only alternative has waiting work -> no hedge
    s = router.remote({"tokens": [1, 2, 3], "max_new_tokens": 2})
    other = next(r for r in reps if r.id != s.replica_id)
    other._depth = 5                         # its queue is backed up
    s.submitted_ts -= 100.0                  # way past any deadline
    router._maybe_hedge()
    assert s.hedge_rid is None
    other._depth = 0                         # capacity appears
    router._maybe_hedge()
    assert s.hedge_rid is not None and s.hedge_replica_id == other.id
    assert router.telemetry.hedges == {"issued": 1}


def test_hedge_race_hedge_wins_exactly_once(tiny_f32):
    """Deterministic hedge race, hedge side wins: the first token
    from the hedge binding resolves the race, the primary's leg is
    unbound + cancelled (slot/pages/prefix refs released on its next
    tick), and the delivered sequence equals the unhedged greedy run
    exactly — at-most-once is structural."""
    from ray_tpu.fleet import FleetRouter
    cfg, _ = tiny_f32
    prompt = _prompt(8, cfg.vocab_size, seed=40)
    ref = _make_replica(tiny_f32, "ref-hw")
    (expected,) = ref.engine.generate([prompt], max_new_tokens=4)

    reps = [_make_replica(tiny_f32, "p0"), _make_replica(tiny_f32, "p1")]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(affinity=False, hedge=True,
                                         hedge_min=0.05),
                         rng_seed=2, telemetry=tel)
    s = router.remote({"tokens": prompt, "max_new_tokens": 4})
    primary = router._replicas[s.replica_id]
    hedge_rep = next(r for r in reps if r.id != primary.id)
    # the primary is "slow": no tick has delivered; force the deadline
    s.submitted_ts -= 10.0
    router._maybe_hedge()
    assert (s.hedge_replica_id, s.hedges) == (hedge_rep.id, 1)
    # step ONLY the hedge replica: its first token wins the race
    for ev in hedge_rep.step():
        router._dispatch(hedge_rep, ev)
    assert s.hedge_rid is None and s.replica_id == hedge_rep.id
    assert tel.hedges == {"issued": 1, "won": 1}
    assert 1 <= len(s.generated) <= 2       # prefill (+maybe decode)
    # the loser's binding is gone: the primary's late tick can no
    # longer deliver anything for this stream (its rid was cancelled)
    before = list(s.generated)
    for ev in primary.step():
        router._dispatch(primary, ev)
    assert s.generated == before
    # drain to completion: exactly one token sequence, greedy-exact
    deadline = time.monotonic() + 5
    while not s.done and time.monotonic() < deadline:
        router.poll()
    assert list(s.generated) == expected and s.error is None
    while primary.has_work() or hedge_rep.has_work():
        router.poll()
    assert all(r.leak_free() for r in reps)


def test_hedge_race_primary_recovers_after_fire(tiny_f32):
    """Deterministic hedge race, primary side recovers AFTER the
    hedge fired: the primary's first token wins, the hedge leg is
    cancelled and counted ``wasted``, its slot/pages/prefix refs
    release, and the output equals the unhedged run exactly."""
    from ray_tpu.fleet import FleetRouter
    cfg, _ = tiny_f32
    prompt = _prompt(19, cfg.vocab_size, seed=41)
    ref = _make_replica(tiny_f32, "ref-pw")
    (expected,) = ref.engine.generate([prompt], max_new_tokens=4)

    reps = [_make_replica(tiny_f32, "q0"), _make_replica(tiny_f32, "q1")]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(affinity=False, hedge=True,
                                         hedge_min=0.05),
                         rng_seed=2, telemetry=tel)
    s = router.remote({"tokens": prompt, "max_new_tokens": 4})
    primary = router._replicas[s.replica_id]
    hedge_rep = next(r for r in reps if r.id != primary.id)
    s.submitted_ts -= 10.0
    router._maybe_hedge()
    assert s.hedge_rid is not None
    hedge_key = (s.hedge_replica_id, s.hedge_rid)
    # the primary recovers: ITS first token resolves the race
    for ev in primary.step():
        router._dispatch(primary, ev)
    assert s.hedge_rid is None and s.replica_id == primary.id
    assert tel.hedges == {"issued": 1, "wasted": 1}
    assert hedge_key not in router._by_rid
    # the hedge replica ticks once to process the cancel: released
    hedge_rep.step()
    assert hedge_rep.leak_free() and not hedge_rep.has_work()
    deadline = time.monotonic() + 5
    while not s.done and time.monotonic() < deadline:
        router.poll()
    assert list(s.generated) == expected and s.error is None
    assert s.retries == 0                    # a hedge is not a failover
    assert all(r.leak_free() for r in reps)


def test_hedged_stream_survives_primary_death(tiny_f32):
    """A hedged stream whose primary DIES promotes the surviving
    binding instead of re-routing: the hedge was the failover (no
    retry consumed, no re-prefill), and the stream completes exactly."""
    from ray_tpu.fleet import FleetRouter
    cfg, _ = tiny_f32
    prompt = _prompt(8, cfg.vocab_size, seed=42)
    ref = _make_replica(tiny_f32, "ref-pd")
    (expected,) = ref.engine.generate([prompt], max_new_tokens=3)

    reps = [_make_replica(tiny_f32, "k0"), _make_replica(tiny_f32, "k1")]
    tel = _tel()
    router = FleetRouter(reps, cfg=_fcfg(affinity=False, hedge=True,
                                         hedge_min=0.05),
                         rng_seed=2, telemetry=tel)
    s = router.remote({"tokens": prompt, "max_new_tokens": 3})
    primary = router._replicas[s.replica_id]
    s.submitted_ts -= 10.0
    router._maybe_hedge()
    assert s.hedge_rid is not None
    primary.alive = False                    # gray turned black
    deadline = time.monotonic() + 5
    while not s.done and time.monotonic() < deadline:
        router.poll()
    assert list(s.generated) == expected and s.error is None
    assert s.retries == 0                    # promoted, not re-routed
    assert tel.hedges == {"issued": 1, "won": 1}
    assert primary.reaped                    # corpse audits clean
    assert all(r.leak_free() for r in reps)


def test_reconciler_degraded_blip_sustained_and_death():
    """Table-driven DEGRADED rows: the router's latency verdict moves
    a RUNNING replica to DEGRADED; a blip re-promotes before the
    dwell; a demotion sustained past the dwell drain-restarts (drain
    + replacement spawn + retire once drained — zero dropped); death
    while DEGRADED escalates to WEDGED (black dominates gray)."""
    from ray_tpu.fleet import DEGRADED, RUNNING
    reps, router, rec, made = _stub_fleet(3, dwell=2.0, backoff=0.0,
                                          slow_factor=3.0)
    for r in reps:
        r.latency = 0.01       # measured and healthy (the median)

    def set_latency(rid, lat):
        router._replicas[rid].latency = lat
        router._update_health()

    # blip: demoted, then the score recovers before the dwell
    set_latency("r2", 0.5)
    assert rec.reconcile(now=1.0) == ["r2: RUNNING->DEGRADED"]
    set_latency("r2", 0.01)
    assert rec.reconcile(now=1.5) == ["r2: DEGRADED->RUNNING"]
    assert rec.demotion_restarts == 0 and made == []

    # sustained: dwell passes -> drain-restart (the only gray path
    # that recycles) with the replacement spawned the same pass
    set_latency("r2", 0.5)
    assert rec.reconcile(now=2.0) == ["r2: RUNNING->DEGRADED"]
    assert rec.reconcile(now=3.5) == []      # dwell not yet served
    acts = rec.reconcile(now=4.1)
    assert "r2: DEGRADED->DRAINING (degraded drain-restart)" in acts
    assert any("STARTING (restore" in a for a in acts)
    assert reps[2].draining and rec.demotion_restarts == 1
    assert len(made) == 1
    # retire once drained; the replacement goes RUNNING
    reps[2]._drained = True
    acts = rec.reconcile(now=4.2)
    assert "r2: DRAINING->STOPPED" in acts
    assert "r2" not in rec.states()
    assert sorted(rec.states().values()).count(RUNNING) == 3

    # death while DEGRADED: WEDGED immediately (no dwell on failures)
    set_latency("r1", 0.5)
    rec.reconcile(now=5.0)
    assert rec.states()["r1"] == DEGRADED
    reps[1].alive = False
    acts = rec.reconcile(now=5.5)
    assert "r1: DEGRADED->WEDGED" in acts
    # backoff=0: the corpse is replaced the same pass (1:1 restart,
    # not a drain) — the gray path never ran
    assert any("RESTARTING" in a for a in acts)
    assert "r1" not in rec.states()
    assert rec.demotion_restarts == 1        # unchanged by the death


def test_gray_failure_acceptance(tiny_f32):
    """THE r19 acceptance test: one replica runs a sustained
    ``serve.tick`` slowdown window mid-traffic (slow, never dead).
    With health-scored routing + hedging ON, every stream completes
    with greedy continuations exactly matching the unfailed run, the
    fleet p99 TTFT beats the mitigation-OFF arm by >= 2x, the slow
    replica is demoted then recycled by the reconciler (DEGRADED ->
    drain-restart) with zero dropped streams and ZERO recompiles, and
    the fleet-wide leak audit passes."""
    from ray_tpu.fleet import FleetRouter, Reconciler, RUNNING
    from ray_tpu.util import chaos
    cfg, _ = tiny_f32
    prompts = [_prompt(8 + i, cfg.vocab_size, seed=50 + i)
               for i in range(9)]
    ref = _make_replica(tiny_f32, "gray-ref")
    expected = ref.engine.generate(prompts, max_new_tokens=4)
    delay, gap = 0.4, 0.05

    def run_arm(mitigate):
        fcfg = _fcfg(retries=2, dwell=0.3, backoff=0.0,
                     slow_factor=3.0 if mitigate else 0.0,
                     hedge=mitigate, hedge_factor=2.0, hedge_min=0.06)
        tag = "m" if mitigate else "u"
        reps = [_make_replica(tiny_f32, f"{tag}{i}") for i in range(3)]
        slow_id = reps[0].id
        router = FleetRouter(reps, cfg=fcfg, affinity=False,
                             rng_seed=1, concurrent_steps=True,
                             telemetry=_tel())
        rec = Reconciler(
            router, lambda rid: _make_replica(tiny_f32, rid),
            target=3, cfg=fcfg)
        chaos.install_faults(
            f"serve.tick[{slow_id}]@1..100000:delay={delay}")
        streams, i = [], 0
        t0 = time.monotonic()
        try:
            while i < len(prompts) or any(not s.done for s in streams):
                now = time.monotonic() - t0
                while i < len(prompts) and i * gap <= now:
                    streams.append(router.remote(
                        {"tokens": prompts[i], "max_new_tokens": 4}))
                    i += 1
                progressed = router.poll()
                if mitigate:
                    rec.reconcile()
                if not progressed:
                    time.sleep(0.002)
                assert time.monotonic() - t0 < 60, "gray arm hung"
            if mitigate:
                # keep reconciling until the chronically slow replica
                # has been recycled: demoted -> DEGRADED -> (dwell)
                # drain-restart -> STOPPED, replacement RUNNING
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    router.poll()
                    rec.reconcile()
                    if (slow_id not in rec.states() and sorted(
                            rec.states().values()).count(RUNNING) == 3):
                        break
                    time.sleep(0.005)
        finally:
            chaos.clear_faults()
        return streams, router, rec, reps, slow_id

    streams_on, router_on, rec_on, reps_on, slow_on = run_arm(True)
    streams_off, router_off, _, reps_off, _ = run_arm(False)

    # zero dropped streams, exact greedy continuations, both arms
    for streams in (streams_on, streams_off):
        assert all(s.done and s.error is None for s in streams)
        for s, want in zip(streams, expected):
            assert list(s.generated) == want
    # mitigation ON beats OFF >= 2x on fleet p99 TTFT: the tail must
    # stop tracking the straggler (delay dwarfs a healthy tick, so
    # the margin is wide even on a noisy box)
    p99 = lambda xs: sorted(xs)[min(len(xs) - 1,       # noqa: E731
                                    int(0.99 * len(xs)))]
    p99_on = p99(router_on.recent_ttfts())
    p99_off = p99(router_off.recent_ttfts())
    assert p99_off >= 2 * p99_on, (p99_on, p99_off)
    # the slow replica was demoted then recycled with zero dropped
    tel = router_on.telemetry.summary()
    assert tel["replica_demotions"] >= 1
    assert rec_on.demotion_restarts == 1
    assert slow_on not in rec_on.states()
    assert sorted(rec_on.states().values()).count(RUNNING) == 3
    # hedge accounting is consistent: every issue resolved one way
    hedges = tel["hedges"]
    assert hedges.get("issued", 0) == \
        hedges.get("won", 0) + hedges.get("wasted", 0)
    # ZERO recompiles anywhere (shared executable cache), and the
    # fleet-wide leak audit passes in both arms
    for router, reps in ((router_on, reps_on), (router_off, reps_off)):
        for r in router.replicas():
            assert r.engine.stats()["compiles"] == {
                "prefill": 0, "prefill_cached": 0, "decode": 0}
        assert router.leak_free()
        assert all(r.leak_free() for r in reps)
    router_on.close()
    router_off.close()


def test_latency_score_decays_when_idle(tiny_f32):
    """Demotion stops a replica's traffic, so its EWMA gets no fresh
    ticks — the score must decay while idle (stale slowness evidence
    ages out, keeping the reconciler's blip-recovers-to-RUNNING arm
    reachable for replicas without continuous work) while an
    in-flight tick's age still floors it."""
    rep = _make_replica(tiny_f32, "idle-decay")
    rep._latency_ewma = 1.0
    rep._last_tick_done_ts = time.monotonic()
    assert rep.latency_score() == pytest.approx(1.0, rel=0.05)
    rep._last_tick_done_ts = time.monotonic() - 60.0
    assert rep.latency_score() < 0.01
    # the decay is slow by design (half-life ~ the reconciler dwell):
    # a short idle gap must NOT flap a demotion inside one episode
    rep._last_tick_done_ts = time.monotonic() - 1.0
    assert rep.latency_score() > 0.5
    rep._tick_t0 = time.monotonic() - 0.4   # step in flight: age floor
    assert rep.latency_score() >= 0.4
    rep._tick_t0 = None
    assert rep.leak_free()


def test_fleet_stream_logprobs_parity(tiny_f32):
    """The fleet stream honors the deployment's payload contract:
    {"logprobs": True} yields {"token", "logprob"} dicts, and the
    values match a direct engine run of the same prompt."""
    from ray_tpu.fleet import FleetRouter
    cfg, _ = tiny_f32
    prompt = _prompt(9, cfg.vocab_size, seed=42)
    ref = _make_replica(tiny_f32, "lpref")
    toks_ref, lps_ref = ref.engine.generate([prompt], max_new_tokens=4,
                                            return_logprobs=True)
    router = FleetRouter([_make_replica(tiny_f32, "lp0")],
                         cfg=_fcfg(), telemetry=_tel())
    out = list(router.remote({"tokens": prompt, "max_new_tokens": 4,
                              "logprobs": True}))
    assert [o["token"] for o in out] == toks_ref[0]
    assert [o["logprob"] for o in out] == pytest.approx(lps_ref[0])


def test_reconciler_ttft_slo_breach_scales_up():
    reps, router, rec, made = _stub_fleet(2, ttft_slo=0.1, dwell=1.0)
    rec.max_replicas = 3
    # queue depth is fine, but TTFT p50 blows the SLO
    for _ in range(8):
        router._record_ttft(0.5)
    assert rec.reconcile(now=1.0) == []
    acts = rec.reconcile(now=2.5)
    assert any("scale-up" in a and "ttft" in a for a in acts)


def test_reconciler_scale_down_drains_newest_after_dwell():
    from ray_tpu.fleet import DRAINING, RUNNING
    reps, router, rec, made = _stub_fleet(2, dwell=1.0)
    rec.target = 1
    # idle must persist a dwell before draining
    assert rec.reconcile(now=0.5) == []
    acts = rec.reconcile(now=2.0)
    (drain_act,) = [a for a in acts if "DRAINING" in a]
    drained_id = drain_act.split(":")[0]
    assert rec.states()[drained_id] == DRAINING
    draining = rec.instances[drained_id].replica
    assert draining.draining                     # admission stopped
    # not drained yet: stays DRAINING, never admits via the router
    assert rec.reconcile(now=3.0) == []
    assert router.remote(
        {"tokens": [1, 2], "max_new_tokens": 1}).replica_id \
        != drained_id
    # in-flight done: retire
    draining._drained = True
    acts = rec.reconcile(now=4.0)
    assert f"{drained_id}: DRAINING->STOPPED" in acts
    assert list(rec.states().values()) == [RUNNING]
    # floor: never drains below target
    for t in (10.0, 20.0):
        assert all("DRAINING" not in a for a in rec.reconcile(now=t))


# ----------------------------------------------------- idle-stream reaper
def test_idle_stream_reaper_frees_dropped_generator(tiny_f32):
    """r10 regression hole closed: a consumer that silently stops
    pumping its stream (generator held but never advanced) no longer
    pins a slot to max_new_tokens — the idle reaper cancels the
    request, frees slot/pages, and leaves a typed StreamIdleError for
    any late reader.  A consumer merely waiting on a slow engine is
    not reaped."""
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.inference.serve_gpt import (GPTDeployment,
                                             StreamIdleError)
    dep = GPTDeployment.func_or_class(
        model="tiny", model_config={"dtype": jnp.float32},
        engine_config=dict(_ENGINE_KW), stream_idle_s=0.03)

    async def main():
        agen = dep({"tokens": [1, 2, 3], "max_new_tokens": 50})
        await agen.__anext__()           # pump once, then go silent,
        deadline = time.monotonic() + 10  # HOLDING the generator (GC
        while time.monotonic() < deadline:  # finalization must not be
            await asyncio.sleep(0.02)       # what frees the slot)
            st = dep.engine.stats()
            if st["active"] == 0 and st["waiting"] == 0:
                break
        st = dep.engine.stats()
        assert dep.streams_reaped == 1
        assert st["active"] == 0
        assert st["free_slots"] == _ENGINE_KW["slots"]
        assert not dep._queues and not dep.engine._requests
        # the reaper fired well before 50 decode ticks were paid
        assert st["ticks"] < 40
        # a late reader raises typed, instead of hanging on a queue
        # the pump no longer feeds
        with pytest.raises(StreamIdleError, match="STREAM_IDLE"):
            async for _ in agen:
                pass

    asyncio.run(asyncio.wait_for(main(), timeout=30))
