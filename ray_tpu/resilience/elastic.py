"""Elastic training: survive a mesh that comes back *smaller*.

Every resilience layer before this one (r15 checkpoint/resume, r16
reconciler, r17 stream cursor) silently assumed the cluster that
resumes has the same device count as the one that died.  TPU slice
preemption routinely returns fewer chips — at fleet scale failures are
the steady state (arXiv:2510.20171) — so a production run must
restore an 8-device checkpoint onto 4 devices, keep training with an
**unchanged global batch** (the concurrency envelope that makes pod
training predictable, arXiv:2011.03641), and re-expand when capacity
returns.  Three pieces:

- :func:`reshard_state` — move a :class:`~ray_tpu.models.training.
  TrainState` (live or a checkpoint's host snapshot) onto any mesh
  whose data/model axes divide the leaf shapes: host-materialize,
  validate divisibility leaf-by-leaf (typed :class:`ReshardError`
  naming the first offending leaf/axis), ``jax.device_put`` onto the
  new shardings.  Checkpoints already store full host arrays, so
  cross-mesh restore is placement, not resharding arithmetic.

- **global-batch invariance** — ``build_gpt_train(accum_steps=k)``
  (``models/training.py``) runs the step as ``k`` scanned microbatches
  with f32 grad accumulation and one optimizer update, so an 8->4
  shrink doubles ``k`` instead of halving the global batch: the
  optimization trajectory continues, the per-device activation
  footprint stays put, and the loss/grads match the unaccumulated
  step to reduction order.

- :func:`run_elastic_train_loop` — the supervisor: deterministic
  ``mesh.loss`` / ``mesh.restore`` chaos sites (``util/chaos.py``)
  drive shrink -> degraded-steps -> expand transitions; on loss it
  snapshots (graceful, the eviction-notice model) or falls back to
  the latest retained checkpoint (hard preemption), rebuilds the mesh
  at the surviving size with the accumulation factor scaled to keep
  the global batch, reshards, and **compiles exactly once per
  distinct topology** (repeat shrinks to a seen size hit the builder
  cache; asserted via the jit cache sizes the loop returns).

Why bit-exactness ends at the collective reduction order: a degraded
mesh sums the same per-example gradients over a different device
partition (4 shards of scanned pairs vs 8 shards), and float addition
does not associate — so an 8->4->8 run's loss sequence tracks the
uninterrupted 8-device run only to within accumulated rounding drift.
The *data* sequence, by contrast, is exact: batches are a pure
function of the cursor, and the loop's cursor accounting is asserted
float-free (``tests/test_elastic.py``).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.parallel.mesh import MeshSpec, validate_divisibility
from ray_tpu.resilience.config import resilience_config
from ray_tpu.util import chaos


class ElasticError(RuntimeError):
    """Base for elastic-training failures (typed, never a bare
    assert): the supervisor distinguishes 'this topology cannot work'
    from ordinary step exceptions."""


class MeshMismatchError(ElasticError):
    """A checkpoint written on one mesh was asked to restore onto a
    different one without ``reshard=True`` — restoring silently would
    either crash in XLA or, worse, change the run's sharding story
    without anyone deciding that."""

    def __init__(self, recorded: MeshSpec, current: MeshSpec):
        super().__init__(
            f"checkpoint was written on mesh [{recorded.describe()}] "
            f"but restore targets [{current.describe()}] — pass "
            "reshard=True (restore_latest) / use reshard_state to "
            "move it deliberately")
        self.recorded = recorded
        self.current = current

    def __reduce__(self):
        return (MeshMismatchError, (self.recorded, self.current))


class ReshardError(ElasticError):
    """A state leaf cannot shard evenly onto the target mesh — raised
    before any ``device_put``, naming the first offending leaf, its
    shape, and the axis product that fails to divide it."""


def _leaf_paths(tree) -> List[str]:
    import jax
    keystr = jax.tree_util.keystr
    return [keystr(p) for p, _ in jax.tree.leaves_with_path(tree)]


def _axis_sizes(mesh, entry) -> int:
    """Device count a PartitionSpec entry shards a dim over."""
    import math
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(mesh.shape.get(a, 1) for a in axes)


def validate_resharding(state, shardings) -> None:
    """Raise :class:`ReshardError` unless every ``state`` leaf's
    sharded dims divide evenly over the target shardings' mesh axes.
    (``jax.device_put`` onto an uneven NamedSharding fails deep inside
    XLA with a shape error that names neither the leaf nor the axis —
    this is the loud, typed front door.)"""
    import jax
    state_leaves = jax.tree.leaves(state)
    sh_leaves = jax.tree.leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    if len(state_leaves) != len(sh_leaves):
        raise ReshardError(
            f"state has {len(state_leaves)} leaves but the target "
            f"shardings have {len(sh_leaves)} — not the same "
            "TrainState structure")
    paths = _leaf_paths(state)
    for path, leaf, sh in zip(paths, state_leaves, sh_leaves):
        shape = np.shape(leaf)
        spec = getattr(sh, "spec", None)
        mesh = getattr(sh, "mesh", None)
        if spec is None or mesh is None:
            continue                      # replicated / opaque: free
        for dim, entry in enumerate(spec):
            if dim >= len(shape):
                break
            div = _axis_sizes(mesh, entry)
            if div > 1 and shape[dim] % div:
                raise ReshardError(
                    f"state leaf {path} dim {dim} (size "
                    f"{shape[dim]}) does not divide over mesh axes "
                    f"{entry} (product {div}) — this state cannot "
                    f"reshard onto [{MeshSpec.from_mesh(mesh).describe()}]")


def host_state(state):
    """Device pytree -> host numpy pytree (a consistent cut: blocks
    until every leaf's producer is done — the same barrier the async
    checkpointer snapshots behind).  One implementation, shared with
    ``TrainCheckpointer`` — its np.asarray-not-ascontiguousarray
    constraint (0-d step counter must stay 0-d) is load-bearing for
    restore validation."""
    from ray_tpu.resilience.checkpoint import _host_tree
    return _host_tree(state)


def reshard_state(state, shardings):
    """Move ``state`` (device or host pytree) onto the mesh described
    by ``shardings`` (a matching pytree of ``NamedSharding`` — e.g.
    ``build_gpt_train(...)['state_shardings']`` for the new mesh).

    The state is host-materialized first: cross-mesh ``device_put`` of
    already-committed shards would otherwise resolve placement against
    the *old* mesh's devices, and a genuinely lost device must not be
    touched at all.  Divisibility is validated up front
    (:func:`validate_resharding`) so an impossible target fails as a
    typed :class:`ReshardError`, not an XLA internal error."""
    import jax
    host = host_state(state)
    validate_resharding(host, shardings)
    return jax.device_put(host, shardings)


# ------------------------------------------------------------- the loop
def _shrink_target(current: int, min_devices: int) -> int:
    """Surviving size after a mesh-loss event: half the mesh, floored
    at ``min_devices`` (the host-sim stand-in for 'whatever subset the
    platform reports alive')."""
    return max(min_devices, current // 2)


def run_elastic_train_loop(cfg, *, steps: int,
                           batch_size: int = 8, seq_len: int = 32,
                           seed: int = 0,
                           axis: str = "fsdp",
                           devices=None,
                           degraded_devices: Optional[int] = None,
                           accum_steps: int = 1,
                           optimizer=None,
                           ckpt=None,
                           graceful: Optional[bool] = None,
                           min_devices: Optional[int] = None,
                           telemetry: Optional[bool] = None,
                           straggler=None,
                           on_step: Optional[Callable[[int], None]] = None,
                           topologies: Optional[Dict[int, Dict[str, Any]]]
                           = None) -> Dict[str, Any]:
    """A synthetic-LM training loop that survives mesh shrink/expand —
    the elastic acceptance driver for tests and degraded-restore
    recovery.

    Topology events come from the deterministic chaos sites (armed via
    ``RAY_TPU_FAULTS`` or :func:`~ray_tpu.util.chaos.install_faults`;
    each site counts one hit per step):

    - ``mesh.loss`` — the mesh loses devices: the loop snapshots the
      state (``graceful=True``, the eviction-notice model — zero lost
      steps) or restores the latest retained checkpoint (hard loss;
      the cursor rolls back with it, bounded by the cadence), rebuilds
      at ``degraded_devices`` (default: half, floored at
      ``min_devices``) with ``accum_steps`` scaled by the shrink
      factor so the **global batch is unchanged**, reshards, and keeps
      training.
    - ``mesh.restore`` — capacity returned: same dance back to the
      full mesh, accumulation scaled back down.
    - ``mesh.step`` — gray failure (r19): a ``:delay=S`` window
      stretches the step wall (a straggling host gates the
      synchronous step).  Nothing is lost — but the run is paying the
      straggler's pace.  With a straggler supervisor armed
      (``straggler=True`` / a :class:`~ray_tpu.resilience.straggler.
      StragglerSupervisor` / ``RAY_TPU_STRAGGLER_FACTOR`` > 0), a
      sustained straggle is converted into the same graceful
      shrink a ``mesh.loss`` takes (snapshot -> rebuild at the
      degraded size with the global batch unchanged -> reshard), so
      the run trades the straggler's capacity for its speed;
      expansion still rides ``mesh.restore``.  A straggle already at
      the ``min_devices`` floor is logged and ridden out — unlike a
      declared device loss, the state is intact, so training on is
      correct (just slow).

    Every batch is a pure function of ``(seed, cursor)`` (the
    ``run_train_ckpt_loop`` contract), so the returned
    ``batch_cursors`` list *is* the consumed-data accounting: two runs
    with equal lists trained on identical document sequences, exactly.
    Compiled steps are cached per device count — ``compile_counts``
    reports each topology's jit cache size (the acceptance invariant:
    exactly 1 per distinct mesh, repeat shrinks compile nothing).
    ``topologies``: an externally-held cache dict, shared across runs
    of identical ``(cfg, geometry, optimizer)`` so tests and A/B
    drivers pay each topology's compile once per process (the r15/r17
    shared-fixture precedent); ``builds`` then lists only the
    topologies THIS run had to build.
    """
    import jax

    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.telemetry.config import TelemetryConfig
    from ray_tpu.telemetry.elastic import ElasticTelemetry

    rcfg = resilience_config()
    if graceful is None:
        graceful = rcfg.elastic_graceful
    if min_devices is None:
        min_devices = rcfg.elastic_min_devices
    devices = list(devices if devices is not None else jax.devices())
    n_full = len(devices)
    if degraded_devices is None:
        degraded_devices = _shrink_target(n_full, min_devices)
    if degraded_devices < min_devices:
        raise ElasticError(
            f"degraded_devices={degraded_devices} is below "
            f"min_devices={min_devices} "
            "(RAY_TPU_ELASTIC_MIN_DEVICES) — a loss this deep is "
            "declared fatal, not a target")
    tel_config = (TelemetryConfig(enabled=bool(telemetry))
                  if isinstance(telemetry, bool) else None)
    tel = ElasticTelemetry(config=tel_config)
    tx = optimizer or training.default_optimizer()

    from ray_tpu.resilience.straggler import StragglerSupervisor
    if isinstance(straggler, StragglerSupervisor):
        watch = straggler
    elif straggler is None:
        watch = StragglerSupervisor()      # env-armed (factor 0 = off)
    elif straggler:
        sfactor = rcfg.straggler_factor or 3.0
        watch = StragglerSupervisor(factor=sfactor)
    else:
        watch = StragglerSupervisor(factor=0.0)

    if topologies is None:
        topologies = {}
    builds: List[int] = []

    def topology(n: int) -> Dict[str, Any]:
        if n in topologies:
            return topologies[n]
        if n < 1 or n > n_full:
            raise ElasticError(f"cannot build a {n}-device mesh from "
                               f"{n_full} devices")
        if n_full % n:
            raise ElasticError(
                f"surviving device count {n} does not divide the full "
                f"mesh ({n_full}): the accumulation factor that keeps "
                "the global batch would not be whole")
        k = accum_steps * (n_full // n)
        mesh = make_mesh(**{axis: n}, devices=devices[:n])
        validate_divisibility(mesh, batch=batch_size, accum_steps=k)
        fns = training.build_gpt_train(cfg, mesh, optimizer=tx,
                                       accum_steps=k, telemetry=False)
        topologies[n] = {"mesh": mesh, "fns": fns, "n": n,
                         "spec": MeshSpec.from_mesh(mesh),
                         "accum_steps": k}
        builds.append(n)
        return topologies[n]

    topo = topology(n_full)
    state = topo["fns"]["init_fn"](jax.random.PRNGKey(seed))
    data_key = jax.random.PRNGKey(seed + 1)
    cursor = 0
    tel.record_mesh(n_full)

    losses: List[float] = []
    batch_cursors: List[int] = []
    transitions: List[Dict[str, Any]] = []
    straggler_events: List[int] = []

    def transition(kind: str, target: int, cause: str = "fault"):
        nonlocal state, topo, cursor
        src = topo["n"]
        if target == src:
            return                          # already there: no-op
        t0 = time.monotonic()
        if kind == "shrink" and not graceful and cause != "straggler":
            if ckpt is None:
                raise ElasticError(
                    "hard mesh loss (graceful=False) needs a "
                    "TrainCheckpointer to fall back to")
            # the live state is lost with the mesh, but its SHAPES are
            # the restore target (orbax needs a typed example to give
            # back the TrainState structure, not a raw dict)
            example = {"state": state,
                       "extras": {"data_cursor": np.asarray(0)}}
            restored = ckpt.restore_latest(example=example,
                                           reshard=True)
            if restored is None:
                raise ElasticError(
                    "hard mesh loss with nothing restorable: the run "
                    "is lost (checkpoint before arming mesh.loss)")
            snapshot = restored["state"]
            cursor = int(np.asarray(restored["extras"]["data_cursor"]))
        else:
            # graceful: the eviction notice arrived — final snapshot
            # off the dying mesh (host copy only; the old devices are
            # never touched again after this line)
            snapshot = host_state(state)
        new = topology(target)
        state = reshard_state(snapshot, new["fns"]["state_shardings"])
        dt = time.monotonic() - t0
        topo = new
        transitions.append({"kind": kind, "step": cursor,
                            "from": src, "to": target,
                            "cause": cause,
                            "reshard_s": round(dt, 6)})
        tel.record_transition(kind, dt, n_devices=target)
        # the new topology has a new normal step wall: a straggler
        # baseline carried across it would misfire
        watch.reset()

    while cursor < steps:
        if chaos.should_fire("mesh.loss"):
            target = (_shrink_target(topo["n"], min_devices)
                      if degraded_devices >= topo["n"]
                      else degraded_devices)
            if target >= topo["n"]:
                # already at the floor: the documented contract is
                # that a loss below RAY_TPU_ELASTIC_MIN_DEVICES is
                # FATAL — a 1-device "fleet" may be worse than waiting
                # for quota, and silently ignoring a declared device
                # loss would train on state the event said is gone
                raise ElasticError(
                    f"mesh.loss at the min_devices floor: the "
                    f"{topo['n']}-device mesh cannot shrink below "
                    f"min_devices={min_devices} "
                    "(RAY_TPU_ELASTIC_MIN_DEVICES) — the loss is "
                    "fatal; resume from the latest checkpoint when "
                    "capacity returns")
            transition("shrink", target)
        if chaos.should_fire("mesh.restore"):
            transition("expand", n_full)
        batch = training.synthetic_lm_batch(
            jax.random.fold_in(data_key, cursor), batch_size, seq_len,
            cfg.vocab_size)
        batch_cursors.append(cursor)
        t_step = time.monotonic()
        # the mesh.step slowdown site stretches exactly the window the
        # straggler supervisor watches — an injected gray failure is
        # indistinguishable from a genuinely straggling host
        chaos.maybe_fail("mesh.step")
        state, metrics = topo["fns"]["step_fn"](state, batch)
        losses.append(float(metrics["loss"]))   # blocks: the wall is real
        step_wall = time.monotonic() - t_step
        cursor += 1
        # per-tier baseline: a DCN-crossing step is legitimately
        # slower than an ICI-only one, so each tier judges its own
        step_tier = ("dcn" if topo["mesh"].shape.get("dcn", 1) > 1
                     else "ici")
        if watch.observe(step_wall, tier=step_tier):
            straggler_events.append(cursor - 1)
            tel.record_straggler()
            target = (_shrink_target(topo["n"], min_devices)
                      if degraded_devices >= topo["n"]
                      else degraded_devices)
            if target < topo["n"]:
                # degraded-mesh event via the r18 machinery: ALWAYS a
                # graceful snapshot — unlike a declared loss, the
                # state is intact, the straggler just taxes it
                transition("shrink", target, cause="straggler")
            # at the min_devices floor there is nothing to shed:
            # intact state, so training on (slow) is correct — the
            # event is still counted for the operator
        if ckpt is not None:
            ckpt.maybe_save(state, step=cursor,
                            extras={"data_cursor": cursor},
                            mesh=topo["mesh"],
                            accum_steps=topo["accum_steps"])
        if on_step is not None:
            on_step(cursor)
    if ckpt is not None:
        ckpt.flush()

    compile_counts = {
        n: t["fns"]["step_fn"]._cache_size()
        for n, t in topologies.items()
        if hasattr(t["fns"]["step_fn"], "_cache_size")}
    return {
        "losses": losses,
        "batch_cursors": batch_cursors,
        "transitions": transitions,
        "straggler_events": straggler_events,
        "builds": builds,
        "compile_counts": compile_counts,
        "final_step": int(np.asarray(state.step)),
        "final_devices": topo["n"],
        "accum_steps": topo["accum_steps"],
        "elastic": tel.summary(),
        "checkpoint": (ckpt.telemetry.summary() if ckpt is not None
                       else {"enabled": False}),
    }
