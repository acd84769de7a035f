"""Chaos testing — kill random workers/actors/nodes under load, and
deterministic fault injection for the ML stack.

Parity: the reference's chaos-testing utilities
(``python/ray/_private/test_utils.py`` get_and_run_resource_killer /
WorkerKillerActor shapes, used by the chaos release tests): a
background thread that periodically kills a random victim so fault-
tolerance paths (task retries, actor restarts, lineage reconstruction,
node-death recovery) are exercised for real, not just unit-tested.

**Deterministic faults (r15).**  :class:`ResourceKiller` is random by
design, which is right for soak tests and wrong for acceptance tests:
a recovery *invariant* ("the RL loop survives an actor death with zero
steady-state recompiles") needs the death to land at an exact,
reproducible point.  :class:`FaultPlan` is that: named injection
**sites** in the ML stack call :func:`maybe_fail`/:func:`should_fire`,
and a spec — ``RAY_TPU_FAULTS`` or :func:`install_faults` — arms the
Nth hit of a site to raise :class:`InjectedFault` (or, for action
sites like checkpoint truncation, to return True so the site corrupts
itself).  Current sites:

- ``rl.rollout`` — a rollout actor dies entering its Nth rollout
  (the supervised loop must restart it from the latest weights);
- ``rl.learner`` — the learner dies entering its Nth update (the
  supervised loop must restore it from its checkpoint);
- ``rl.publish`` — the Nth weight publication fails (the loop keeps
  training; actors stay on the previous version);
- ``infer.decode`` — the Nth engine decode tick raises *before* the
  compiled step dispatches (donated buffers untouched — the engine
  stays drainable);
- ``ckpt.write`` — the Nth background checkpoint write fails;
- ``ckpt.truncate`` — the Nth checkpoint write is truncated on disk
  *after* writing (the resume path must fall back to the previous
  retained snapshot, loudly);
- ``serve.replica`` — the Nth fleet-replica engine tick kills the
  replica mid-traffic (the router must fail its in-flight streams
  over to healthy replicas; the reconciler must restore the target
  count with zero steady-state recompiles);
- ``serve.route`` — the Nth routed submit fails in flight (the
  router must re-route to another replica, counting the retry);
- ``data.read`` — the Nth shard-reader fetch dies (the data plane
  must restart the reader and re-issue the fetch verbatim —
  exactly-once sample accounting, no drop, no dup);
- ``data.pack`` — the Nth batch assembly dies before mutating packer
  state (the plane retries; the replayed batch is bit-identical);
- ``data.stall`` — the Nth shard read sleeps (slow-shard
  backpressure: the bounded prefetch queue drains and the trainer's
  ``data_stall_seconds`` histogram shows the block).  Prefer the
  ``:delay=S`` grammar; a bare ``data.stall@N`` entry is the
  deprecated alias that sleeps ``RAY_TPU_DATA_STALL_S``;
- ``mesh.loss`` — at the Nth elastic-loop step the training mesh
  loses devices (slice preemption): the loop snapshots (graceful) or
  falls back to the latest retained checkpoint, rebuilds at the
  surviving size with the gradient-accumulation factor scaled to keep
  the global batch, and reshards (``resilience/elastic.py``);
- ``mesh.restore`` — at the Nth step the lost capacity returns: the
  loop re-expands to the full mesh the same way;
- ``serve.tick`` — per-replica engine-tick latency (the r19 gray-
  failure site): a ``:delay=`` entry stretches the tick's wall time
  instead of killing anything — the slow-but-alive replica the
  health-scored router must demote and hedge around.  Counted twice:
  once fleet-wide as ``serve.tick`` and once per replica as
  ``serve.tick[<replica_id>]``, so a plan can slow exactly one
  replica for a sustained window deterministically;
- ``mesh.step`` — per-step train-loop latency: a ``:delay=`` window
  stretches step wall time (a straggling host gates the synchronous
  step), which the straggler supervisor must detect and convert into
  a degraded-mesh shrink instead of stalling the run forever;
- ``serve.handoff`` — the r20 disaggregated prefill→decode KV-page
  handoff: fires on BOTH legs of every transfer (once on the export
  leg, before the pages leave the prefill replica's allocator, and
  once on the import leg, before the decode side admits), so hits
  count two per handoff and a plan can fault either side — or
  ``:delay=`` the transfer itself.  Any fault degrades to the
  re-prefill-from-prompt failover with the held pages and the
  in-flight store object released (the disagg leak audit covers
  both);
- ``serve.adapter_load`` — the r25 multi-tenant adapter-cache miss
  leg: fires as a replica resolves a request's ``model_id`` that is
  not yet resident in its LoRA bank (cache hits never pay the site),
  before the store checkout, or ``:delay=`` stretches the load (a
  slow adapter fetch).  A fault surfaces as the typed
  ``AdapterUnavailableError``: submit-time rejections re-route to
  another replica, a resolution-time fault retires the waiting
  request with the error on its stream — either way degraded, never
  a hang — and resident tenants keep decoding untouched.

Spec grammar: comma-separated entries::

    site[@N[..M]][:delay=S]

``N`` is the 1-based hit index (bare ``site`` means ``site@1``).
Without ``:delay=``, the entry is a **fault**: hit ``N`` raises (or,
for action sites, returns True) exactly once; a hit *range* is
meaningless for faults and is rejected.  With ``:delay=S``, the entry
is a **slowdown**: every hit in ``[N, M]`` (``M`` defaults to ``N``)
sleeps ``S`` seconds inside the site before proceeding — gray failure,
replayable because it is driven off the same deterministic hit
counters.  E.g. ``RAY_TPU_FAULTS="rl.rollout@3,serve.tick[r0]@5..40:
delay=0.1,data.read@2:delay=0.5"``.

Hit counters are lock-protected: the ``StreamingLoader`` producer
thread, hedged standby readers and the main thread may count sites
concurrently, and deterministic replay must not race.
"""

from __future__ import annotations

import os
import random
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import ray_tpu


# ---------------------------------------------------------------- faults
class InjectedFault(RuntimeError):
    """Raised by an armed fault-injection site (never by real code
    paths) — supervisors treat it like any other death, tests can
    assert it specifically."""

    def __init__(self, site: str, hit: int):
        super().__init__(f"injected fault at site {site!r} (hit {hit})")
        self.site = site
        self.hit = hit

    def __reduce__(self):
        # rebuild from constructor args, not the message — injected
        # faults cross process boundaries (killed remote actors)
        return (InjectedFault, (self.site, self.hit))


class FaultPlan:
    """Parsed fault spec: deterministic per-site hit counters.

    ``fires(site)`` counts one hit of ``site``, sleeps any armed
    slowdown for this hit, and reports whether an armed fault triggers
    on exactly this hit.  Counters are process-global per plan and
    lock-protected (producer threads and hedged standby readers count
    sites concurrently with the main thread), so a fixed spec +
    deterministic call order reproduces the same failure point every
    run.  ``fired`` logs every triggered ``(site, hit)`` and
    ``slowed`` every injected ``(site, hit, seconds)`` so tests can
    assert the gray failure actually landed.
    """

    def __init__(self, spec: str = ""):
        self._armed: Dict[str, List[int]] = {}
        # site -> [(first_hit, last_hit, delay_s)] slowdown windows
        self._delays: Dict[str, List[Tuple[int, int, float]]] = {}
        self._hits: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.fired: List[Tuple[str, int]] = []
        self.slowed: List[Tuple[str, int, float]] = []
        self.spec = spec.strip()
        for entry in self.spec.split(","):
            entry = entry.strip()
            if not entry:
                continue
            head, _, tail = entry.partition(":")
            delay = None
            if tail:
                key, _, val = tail.partition("=")
                if key.strip() != "delay" or not val:
                    raise ValueError(
                        f"bad RAY_TPU_FAULTS entry {entry!r}: the "
                        "only site modifier is ':delay=S' (seconds)")
                try:
                    delay = float(val)
                except ValueError:
                    raise ValueError(
                        f"bad RAY_TPU_FAULTS entry {entry!r}: "
                        f"delay {val!r} is not a number of seconds")
                if delay < 0:
                    raise ValueError(
                        f"bad RAY_TPU_FAULTS entry {entry!r}: delay "
                        "must be >= 0 seconds")
            site, _, at = head.partition("@")
            site = site.strip()
            lo, _, hi = at.partition("..")
            try:
                first = int(lo) if lo else 1
                last = int(hi) if hi else first
            except ValueError:
                raise ValueError(
                    f"bad RAY_TPU_FAULTS entry {entry!r}: expected "
                    "'site', 'site@N' or 'site@N..M' (1-based hit "
                    "indices)")
            if first < 1 or last < first:
                raise ValueError(
                    f"bad RAY_TPU_FAULTS entry {entry!r}: hit index "
                    "must be >= 1 (and N <= M for a window)")
            if delay is None:
                if hi:
                    raise ValueError(
                        f"bad RAY_TPU_FAULTS entry {entry!r}: a hit "
                        "range only makes sense for a slowdown — add "
                        "':delay=S' (a fault fires once, at one hit)")
                self._armed.setdefault(site, []).append(first)
            else:
                self._delays.setdefault(site, []).append(
                    (first, last, delay))

    def fires(self, site: str) -> bool:
        """Count one hit of ``site``; sleep this hit's armed slowdown
        (if any); True iff an armed fault triggers on exactly this hit
        (each armed entry fires at most once)."""
        with self._lock:
            hit = self._hits.get(site, 0) + 1
            self._hits[site] = hit
            delay = 0.0
            for first, last, d in self._delays.get(site, ()):
                if first <= hit <= last:
                    delay += d
            if delay > 0:
                self.slowed.append((site, hit, delay))
            fired = hit in self._armed.get(site, ())
            if fired:
                self.fired.append((site, hit))
        if delay > 0:           # sleep OUTSIDE the lock: a slowed
            time.sleep(delay)   # site must not block other counters
        return fired

    def hits(self, site: str) -> int:
        with self._lock:
            return self._hits.get(site, 0)

    def slowdown_s(self, site: str) -> float:
        """Total injected delay the plan has charged to ``site`` so
        far (test/telemetry accounting)."""
        with self._lock:
            return sum(d for s, _, d in self.slowed if s == site)


_PLAN: Optional[FaultPlan] = None
_PLAN_FROM_ENV = False


def install_faults(spec: str) -> FaultPlan:
    """Arm a fault plan programmatically (tests / drivers); returns it
    so the caller can assert on ``plan.fired``."""
    global _PLAN, _PLAN_FROM_ENV
    _PLAN = FaultPlan(spec)
    _PLAN_FROM_ENV = True       # explicit install wins over the env
    return _PLAN


def clear_faults() -> None:
    global _PLAN, _PLAN_FROM_ENV
    _PLAN = None
    _PLAN_FROM_ENV = False


def fault_plan() -> Optional[FaultPlan]:
    """The active plan: an installed one, else lazily from the
    ``RAY_TPU_FAULTS`` env spec (read once), else None."""
    global _PLAN, _PLAN_FROM_ENV
    if _PLAN is None and not _PLAN_FROM_ENV:
        spec = os.environ.get("RAY_TPU_FAULTS", "")
        _PLAN_FROM_ENV = True
        if spec.strip():
            _PLAN = FaultPlan(spec)
    return _PLAN


def should_fire(site: str) -> bool:
    """Count a hit of an *action* site (the site corrupts something
    itself when True — e.g. truncating a just-written checkpoint)."""
    plan = fault_plan()
    return plan.fires(site) if plan is not None else False


def maybe_fail(site: str) -> None:
    """Count a hit of a *raise* site; raises :class:`InjectedFault`
    when an armed fault triggers.  Free when no plan is armed."""
    plan = fault_plan()
    if plan is not None and plan.fires(site):
        hit = plan.hits(site)
        # r24: every injected fault is a flight-recorder anomaly —
        # lazy import keeps the un-armed fast path free of telemetry
        from ray_tpu.telemetry import trace as trace_mod
        trace_mod.on_injected_fault(site, hit)
        raise InjectedFault(site, hit)


class ResourceKiller:
    """Kill a random victim every ``interval_s`` while running.

    ``kind``: "worker" (SIGKILL a task worker process), "actor"
    (ray_tpu.kill a random live actor), or "node" (terminate a random
    non-head node process).
    """

    def __init__(self, kind: str = "worker", interval_s: float = 1.0,
                 max_kills: Optional[int] = None,
                 rng_seed: Optional[int] = None):
        assert kind in ("worker", "actor", "node")
        self.kind = kind
        self.interval_s = interval_s
        self.max_kills = max_kills
        self.kills: List[str] = []
        self._rng = random.Random(rng_seed)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- victim selection ---------------------------------------------
    def _pick_worker_pid(self) -> Optional[int]:
        # pids via task events would be racy; read the head node
        # manager's live worker table instead
        from ray_tpu._private.worker import global_node
        nm = global_node().node_manager
        with nm._lock:
            pids = [w.proc.pid for w in nm._workers.values()
                    if w.proc is not None and w.state == "busy"]
        return self._rng.choice(pids) if pids else None

    def _pick_actor(self):
        from ray_tpu.util.state import list_actors
        rows = [r for r in list_actors() if r["state"] == "ALIVE"
                and not (r.get("name") or "").startswith("__")]
        if not rows:
            return None
        return bytes.fromhex(self._rng.choice(rows)["actor_id"])

    def _pick_node(self) -> Optional[bytes]:
        from ray_tpu._private.worker import global_node
        extra = [nid for nid, proc in global_node()._extra_nodes
                 if proc.poll() is None]
        return self._rng.choice(extra) if extra else None

    # -- kill actions --------------------------------------------------
    def _kill_once(self) -> bool:
        import os
        import signal
        if self.kind == "worker":
            pid = self._pick_worker_pid()
            if pid is None:
                return False
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                return False
            self.kills.append(f"worker pid={pid}")
        elif self.kind == "actor":
            aid = self._pick_actor()
            if aid is None:
                return False
            from ray_tpu._private.worker import global_worker
            global_worker().kill_actor(aid, no_restart=False)
            self.kills.append(f"actor {aid.hex()[:12]}")
        else:
            nid = self._pick_node()
            if nid is None:
                return False
            from ray_tpu._private.worker import global_node
            global_node().remove_node(nid)
            self.kills.append(f"node {nid.hex()[:12]}")
        return True

    # -- lifecycle -----------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            if self.max_kills is not None and \
                    len(self.kills) >= self.max_kills:
                return
            try:
                self._kill_once()
            except Exception:  # noqa: BLE001 — chaos must not crash
                pass

    def start(self) -> "ResourceKiller":
        if self.kind in ("worker", "node"):
            from ray_tpu._private.worker import global_node
            if getattr(global_node(), "node_manager", None) is None:
                raise ValueError(
                    f"chaos kind={self.kind!r} needs the head driver "
                    "(an attached driver has no local node manager)")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"chaos-{self.kind}")
        self._thread.start()
        return self

    def stop(self) -> List[str]:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        return list(self.kills)

    def __enter__(self) -> "ResourceKiller":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
