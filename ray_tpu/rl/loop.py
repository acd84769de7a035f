"""The closed loop: actors generate, the learner trains, weights flow.

``run_rl_loop`` wires the pieces into the Podracer/Sebulba shape
(arXiv:2104.06272): rollout actors on one side (each an
:class:`~ray_tpu.inference.InferenceEngine` replica), policy-gradient
learner(s) on the other (:func:`~ray_tpu.models.training.
build_gpt_rl_train`, optionally hosted on the RLlib
:class:`~ray_tpu.rllib.core.learner_group.LearnerGroup`), meeting
through :class:`~ray_tpu.rl.replay.WeightStore` (versioned snapshots,
object store when a session is up) and
:class:`~ray_tpu.rl.replay.ReplayQueue` (bounded, hard staleness
bound).  The driver sequences one producer/consumer round per learner
step — actors re-sync to the latest publication before every rollout,
so actor-side lag is bounded by the publish cadence and queue-side lag
by ``max_lag``, deterministically (fixed seeds reproduce the whole
loop, which is what makes the reward-improves acceptance test and the
host-sim bench meaningful).

The default task is the programmatic length-penalized target-token
reward (:mod:`ray_tpu.rl.reward`) — an easy smooth objective whose
expected value must rise under a correct policy gradient.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ray_tpu.rl.config import RLConfig, rl_config
from ray_tpu.rl.learner import InProcessLearner, LearnerGroupAdapter
from ray_tpu.rl.replay import ReplayQueue, WeightStore
from ray_tpu.rl.reward import target_token_reward
from ray_tpu.rl.rollout import RolloutActor


def run_rl_loop(cfg, *, steps: int,
                rlcfg: Optional[RLConfig] = None,
                reward_fn: Optional[Callable] = None,
                prompt: Optional[Sequence[int]] = None,
                prompt_source=None,
                prompt_len: int = 4,
                eos_token: Optional[int] = None,
                seed: int = 0,
                lr: float = 1e-3,
                mesh=None,
                optimizer=None,
                num_learners: int = 0,
                engine_kwargs: Optional[Dict[str, Any]] = None,
                telemetry: Optional[bool] = None) -> Dict[str, Any]:
    """Run ``steps`` learner updates of the actor/learner loop.

    ``num_learners=0`` runs the learner in-process (host-sim parity
    tests); ``>= 1`` hosts it on the RLlib
    LearnerGroup (requires an initialized ray_tpu session) with the
    group's object-store snapshot as the publication path.  Engines
    across actor replicas share one executable cache.

    ``prompt_source``: a :class:`~ray_tpu.data.DocumentSource` (or a
    prebuilt :class:`~ray_tpu.rl.rollout.PromptDataset`) — each
    learner round draws its ``rlcfg.batch`` prompts from the
    deterministic r17 document schedule instead of repeating one fixed
    prompt; the final prompt cursor is returned as
    ``result["prompt_cursor"]`` for preemption-proof resume.

    Returns a result dict: per-step ``history`` (learner metrics +
    rollout reward), the ``reward_curve`` (rollout-side mean reward
    per learner step — the policy-improvement signal), queue/staleness
    counters, the telemetry summary and final engine stats.
    """
    rlcfg = rlcfg or rl_config()
    rng = np.random.RandomState(seed)
    prompt_ds = None
    if prompt_source is not None:
        from ray_tpu.rl.rollout import PromptDataset
        prompt_ds = (prompt_source
                     if isinstance(prompt_source, PromptDataset)
                     else PromptDataset(prompt_source,
                                        prompt_len=prompt_len))
        prompt_len = prompt_ds.prompt_len
    if prompt is None:
        prompt = [int(t) for t in
                  rng.randint(0, cfg.vocab_size, prompt_len)]
    prompts = [list(prompt)] * rlcfg.batch   # shared context: RLOO's
    seq_len = len(prompt) + rlcfg.horizon    # leave-one-out wants it
    if reward_fn is None:
        target = int(rng.randint(0, cfg.vocab_size))
        reward_fn = target_token_reward(target,
                                        length_penalty=1.0 / max(
                                            rlcfg.horizon, 1),
                                        eos_token=eos_token)

    from ray_tpu.telemetry.rl import RLTelemetry
    tel = RLTelemetry(config=None if telemetry is None else
                      _tel_config(telemetry))

    if num_learners >= 1:
        if rlcfg.batch % num_learners:
            # LearnerGroup.update trims the batch to a multiple of the
            # world size — a non-dividing batch would silently discard
            # trajectories (actor compute) on every learner step
            raise ValueError(
                f"rollout batch {rlcfg.batch} is not divisible by "
                f"num_learners={num_learners}: the learner group would "
                "silently drop the remainder rows every step "
                "(RAY_TPU_RL_BATCH)")
        if optimizer is not None or mesh is not None:
            # silently training with a different optimizer/mesh than
            # the caller pinned would invalidate any A/B against the
            # in-process arm — refuse instead
            raise ValueError("optimizer/mesh overrides are in-process-"
                             "learner options; the LearnerGroup-hosted "
                             "path (num_learners >= 1) builds its own "
                             "per-actor mesh and adam optimizer (lr=)")
        learner = LearnerGroupAdapter(cfg, num_learners=num_learners,
                                      baseline=rlcfg.baseline, lr=lr,
                                      seed=seed)
    else:
        learner = InProcessLearner(cfg, mesh=mesh,
                                   baseline=rlcfg.baseline, lr=lr,
                                   optimizer=optimizer, seed=seed)
    store = WeightStore(use_object_store=num_learners >= 1)
    # put_timeout pinned to 0: this driver runs producer and consumer
    # on one thread, so a timed put (RAY_TPU_RL_PUT_TIMEOUT) would
    # wait for a pop that cannot happen until it returns — the
    # hold-and-retry `pending` mechanism below is the backpressure
    # path here
    queue = ReplayQueue(rlcfg.queue, max_lag=rlcfg.max_lag,
                        overflow=rlcfg.overflow, put_timeout=0)

    def publish():
        t0 = time.monotonic()
        if isinstance(learner, LearnerGroupAdapter):
            version, ref = learner.publish_ref()
            version = store.publish(ref, version=version)
        else:
            version = store.publish(learner.params_host())
        tel.record_publish(time.monotonic() - t0, version=version)
        return version

    publish()                                # version 1 seeds actors
    _, params0 = store.latest()
    shared_exec: Dict[Any, Any] = {}
    ekw = dict(engine_kwargs or {})
    ekw.setdefault("executable_cache", shared_exec)
    ekw.setdefault("telemetry", False)
    actors = [RolloutActor(cfg, params0, actor_id=i,
                           temperature=rlcfg.temperature,
                           eos_token=eos_token, engine_kwargs=ekw)
              for i in range(rlcfg.actors)]
    for actor in actors:
        actor.engine.param_version = store.version

    history: List[Dict[str, float]] = []
    reward_curve: List[float] = []
    learner_steps = 0
    rollout_seed = seed * 1_000_003
    # under the "wait" overflow policy a rejected put means
    # backpressure: the actor holds its batch and retries before
    # rolling a new one (no trajectory silently discarded)
    pending: Dict[int, Any] = {}
    try:
        while learner_steps < steps:
            # -------- held batches first: a held batch is strictly
            # older than any fresh rollout, so it must win the freed
            # queue space — retrying inline per-actor would let
            # earlier actors re-fill the queue every round and starve
            # the held one forever
            for aid in list(pending):
                if queue.put(pending[aid]):
                    del pending[aid]
                else:
                    tel.record_backpressure()
            # -------- actor side: one rollout per replica, freshest
            # params first (the actor-side staleness contract: sync
            # before every rollout, so an actor's params never lag the
            # latest publication at generation time)
            for actor in actors:
                if actor.actor_id in pending:
                    continue                # backpressured: no rollout
                if actor.param_version != store.version:
                    version, params = store.latest()
                    actor.sync(version, params)
                rollout_seed += rlcfg.batch
                if prompt_ds is not None:
                    prompts = prompt_ds.next_prompts(rlcfg.batch)
                batch = actor.rollout(prompts, horizon=rlcfg.horizon,
                                      seq_len=seq_len,
                                      reward_fn=reward_fn,
                                      seed=rollout_seed)
                tel.record_rollout(batch.wall_s,
                                   tokens=batch.gen_tokens,
                                   param_version=batch.param_version)
                if not queue.put(batch):
                    tel.record_backpressure()
                    pending[actor.actor_id] = batch
            # -------- learner side: drain what is fresh enough
            while learner_steps < steps:
                batch = queue.pop(store.version)
                if batch is None:
                    break
                lag = store.version - batch.param_version
                t0 = time.monotonic()
                metrics = learner.update(batch.as_learner_batch())
                tel.record_learner_step(time.monotonic() - t0,
                                        version_lag=lag)
                learner_steps += 1
                metrics["rollout_reward_mean"] = float(
                    np.mean(batch.rewards))
                metrics["param_version_lag"] = float(lag)
                history.append(metrics)
                reward_curve.append(metrics["rollout_reward_mean"])
                if learner_steps % rlcfg.publish_every == 0:
                    publish()
    finally:
        leftover = queue.drain() + list(pending.values())
        if isinstance(learner, LearnerGroupAdapter):
            learner.stop()
    tel.record_queue_counters(drops_stale=queue.drops_stale,
                              drops_overflow=queue.drops_overflow)
    leaked = [a.actor_id for a in actors if not a.idle()]
    if leaked:
        # a real check, not an assert: it must survive python -O, and
        # a slot/page leak here means the engine invariants broke
        raise RuntimeError(f"rollout engines {leaked} did not drain "
                           "clean at shutdown (slots/pages still held)")
    return {
        "steps": learner_steps,
        "history": history,
        "reward_curve": reward_curve,
        "leftover_batches": len(leftover),
        "drops_stale": queue.drops_stale,
        "drops_overflow": queue.drops_overflow,
        "param_version": store.version,
        "publishes": store.publish_count,
        "telemetry": tel.summary(),
        "engine_stats": [a.engine.stats() for a in actors],
        "actors": [a.engine for a in actors],
        "learner": learner,
        "prompt_cursor": (prompt_ds.cursor_array()
                          if prompt_ds is not None else None),
    }


def _tel_config(enabled: bool):
    from ray_tpu.telemetry.config import TelemetryConfig
    return TelemetryConfig(enabled=bool(enabled))
