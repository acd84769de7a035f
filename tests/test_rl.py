"""RL subsystem tests: policy-gradient parity, weight-publication
zero-recompile/donation invariants, staleness bounds, and the
end-to-end actor/learner proof (reward improves under REINFORCE/RLOO
on the host-sim mesh)."""

import numpy as np
import pytest


# --------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def tiny_rl():
    """A tiny GPT small enough that the whole loop runs in seconds:
    vocab 128 keeps the target-token task learnable in a handful of
    REINFORCE steps."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig, init_params
    cfg = GPTConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                    max_seq=64, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# engines across RL tests share one executable cache (same geometry ->
# same AOT executables; the test_inference.py pattern)
_EXEC_CACHE = {}
_ENGINE_KW = {"slots": 6, "page_size": 16, "buckets": (16,),
              "telemetry": False, "executable_cache": _EXEC_CACHE}


# ----------------------------------------------------------------- config
def test_rl_config_env_knobs(monkeypatch):
    from ray_tpu.rl import rl_config
    cfg = rl_config(refresh=True)
    assert (cfg.actors, cfg.batch, cfg.horizon) == (1, 8, 16)
    assert (cfg.queue, cfg.max_lag, cfg.overflow) == (4, 1, "drop")
    assert (cfg.publish_every, cfg.baseline) == (1, "rloo")
    assert cfg.temperature == 1.0
    monkeypatch.setenv("RAY_TPU_RL_ACTORS", "3")
    monkeypatch.setenv("RAY_TPU_RL_BATCH", "4")
    monkeypatch.setenv("RAY_TPU_RL_HORIZON", "8")
    monkeypatch.setenv("RAY_TPU_RL_QUEUE", "2")
    monkeypatch.setenv("RAY_TPU_RL_MAX_LAG", "2")
    monkeypatch.setenv("RAY_TPU_RL_OVERFLOW", "wait")
    monkeypatch.setenv("RAY_TPU_RL_PUBLISH_EVERY", "4")
    monkeypatch.setenv("RAY_TPU_RL_BASELINE", "mean")
    monkeypatch.setenv("RAY_TPU_RL_TEMPERATURE", "0.7")
    cfg = rl_config(refresh=True)
    assert (cfg.actors, cfg.batch, cfg.horizon) == (3, 4, 8)
    assert (cfg.queue, cfg.max_lag, cfg.overflow) == (2, 2, "wait")
    assert (cfg.publish_every, cfg.baseline) == (4, "mean")
    assert cfg.temperature == 0.7
    # unknown/invalid values fall back loudly, not silently crash
    monkeypatch.setenv("RAY_TPU_RL_OVERFLOW", "bogus")
    monkeypatch.setenv("RAY_TPU_RL_BASELINE", "gae")
    monkeypatch.setenv("RAY_TPU_RL_MAX_LAG", "-1")
    monkeypatch.setenv("RAY_TPU_RL_QUEUE", "0")
    monkeypatch.setenv("RAY_TPU_RL_TEMPERATURE", "0.0")
    cfg = rl_config(refresh=True)
    assert cfg.overflow == "drop" and cfg.baseline == "rloo"
    assert cfg.max_lag == 0 and cfg.queue == 4
    # temperature <= 0 = greedy rollouts = zero advantages everywhere;
    # must fall back loudly, not degenerate the estimator silently
    assert cfg.temperature == 1.0
    for name in ("ACTORS", "BATCH", "HORIZON", "QUEUE", "MAX_LAG",
                 "OVERFLOW", "PUBLISH_EVERY", "BASELINE",
                 "TEMPERATURE"):
        monkeypatch.delenv(f"RAY_TPU_RL_{name}", raising=False)
    rl_config(refresh=True)


# ----------------------------------------------------------------- reward
def test_target_token_reward():
    from ray_tpu.rl import target_token_reward
    r = target_token_reward(7)
    assert r([7, 1, 7, 7]) == 3.0
    assert r([]) == 0.0
    # length penalty prices every non-EOS token; EOS is excluded from
    # both the hits and the length
    r = target_token_reward(7, length_penalty=0.5, eos_token=9)
    assert r([7, 1, 7, 9]) == 2.0 - 0.5 * 3
    assert r([9]) == 0.0


def test_trajectories_to_batch_layout():
    from ray_tpu.rl import trajectories_to_batch
    out = trajectories_to_batch([[5, 6], [5, 6, 7]],
                                [[10, 11, 12], [20]], seq_len=8)
    tokens, targets = out["tokens"], out["targets"]
    assert tokens.shape == targets.shape == (2, 8)
    assert list(tokens[0, :5]) == [5, 6, 10, 11, 12]
    assert list(tokens[1, :4]) == [5, 6, 7, 20]
    # position t predicts token t+1; only sampled tokens are actions
    assert list(targets[0]) == [-1, 10, 11, 12, -1, -1, -1, -1]
    assert list(targets[1]) == [-1, -1, 20, -1, -1, -1, -1, -1]
    with pytest.raises(ValueError, match="seq_len"):
        trajectories_to_batch([[1, 2]], [[3, 4]], seq_len=3)


# ------------------------------------------------------------- advantages
def test_rl_advantages():
    import jax.numpy as jnp

    from ray_tpu.models.training import rl_advantages
    r = jnp.array([1.0, 2.0, 6.0])
    # RLOO: baseline = mean of the OTHER rewards
    np.testing.assert_allclose(np.asarray(rl_advantages(r, "rloo")),
                               [1 - 4.0, 2 - 3.5, 6 - 1.5], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rl_advantages(r, "mean")),
                               np.asarray(r) - 3.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rl_advantages(r, "none")),
                               np.asarray(r))
    # B=1: no "other" to leave out — rloo degrades to raw rewards
    one = jnp.array([3.0])
    np.testing.assert_allclose(np.asarray(rl_advantages(one, "rloo")),
                               [3.0])
    with pytest.raises(ValueError, match="baseline"):
        rl_advantages(r, "gae")


# ------------------------------------------------------- learner parity
def test_learner_grads_match_hand_computed_pg(tiny_rl):
    """The tentpole parity: the sharded ``build_gpt_rl_train`` gradient
    on the 8-device host-sim mesh (fsdp x tp) matches a hand-written
    single-device REINFORCE/RLOO gradient on a fixed trajectory
    batch, per parameter."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import training
    from ray_tpu.models.gpt import forward
    from ray_tpu.parallel.mesh import make_mesh
    cfg, params = tiny_rl
    mesh = make_mesh(fsdp=4, tp=2, devices=jax.devices())
    fns = training.build_gpt_rl_train(cfg, mesh, baseline="rloo")

    rng = np.random.RandomState(1)
    B, S = 4, 20
    tokens = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    targets = np.full((B, S), -1, np.int32)
    targets[:, 7:15] = tokens[:, 8:16]       # the "completion" window
    rewards = rng.randn(B).astype(np.float32)
    batch = {"tokens": tokens, "targets": targets, "rewards": rewards}

    def hand_loss(p):
        logits, _ = forward(p, jnp.array(tokens), cfg)
        lp = jax.nn.log_softmax(logits, -1)
        chosen = jnp.take_along_axis(
            lp, jnp.maximum(jnp.array(targets), 0)[..., None],
            -1)[..., 0]
        mask = (jnp.array(targets) >= 0).astype(jnp.float32)
        r = jnp.array(rewards)
        adv = (B * r - jnp.sum(r)) / (B - 1)      # RLOO, by hand
        return -jnp.mean(adv * jnp.sum(chosen * mask, -1))

    # jit the reference too: the op-by-op eager gradient costs 2x the
    # jitted compile on this CPU box, for the same numbers
    hand = jax.jit(jax.grad(hand_loss))(params)
    (loss, metrics), grads = fns["pg_grad_fn"](params, batch)
    assert float(loss) == pytest.approx(float(hand_loss(params)),
                                        rel=1e-5)
    assert metrics["action_tokens"] == 4 * 8
    for (ga, gb) in zip(jax.tree.leaves(grads), jax.tree.leaves(hand)):
        a, b = np.asarray(ga), np.asarray(gb)
        denom = np.max(np.abs(b)) + 1e-12
        assert np.max(np.abs(a - b)) / denom < 1e-4
    # (the full donated step_fn — params actually moving, metric
    # schema — is covered on the cheap 1-device mesh by every loop
    # test below (InProcessLearner drives step_fn); compiling it here
    # too would double this test's tier-1 cost for no new coverage)


# --------------------------------------------------- weight publication
def test_weight_publication_zero_recompiles_and_donation(tiny_rl):
    """The acceptance contract: >= 3 published param versions hot-swap
    into a live engine with the compile counters frozen at
    {prefill: K, decode: 1}, each swap deleting the previous snapshot
    (donated-buffer semantics — no steady-state allocation growth)."""
    import jax

    from ray_tpu.inference import InferenceEngine, SamplingParams
    cfg, params = tiny_rl
    engine = InferenceEngine(cfg, params, **_ENGINE_KW)
    prompt = list(np.random.RandomState(5).randint(0, cfg.vocab_size,
                                                   9))
    engine.generate([prompt], max_new_tokens=4)
    compiles0 = dict(engine.compile_counts)
    assert compiles0 == {"prefill": 1, "prefill_cached": 0,
                         "decode": 1}
    assert engine.stats()["param_version"] == 0

    host = jax.tree.map(np.asarray, params)
    live_after_first = None
    prev = None
    for v in (1, 2, 3, 4):
        # swap mid-traffic: a sequence is actively decoding while the
        # new version lands
        engine.submit(prompt, max_new_tokens=5,
                      sampling=SamplingParams(temperature=1.0, seed=v))
        engine.step()
        assert engine.set_params(host, version=v) == v
        if prev is not None:
            # the previous snapshot's buffers are gone, eagerly
            assert all(leaf.is_deleted()
                       for leaf in jax.tree.leaves(prev))
        prev = engine.params
        while engine.has_work():
            engine.step()
        if v == 1:
            live_after_first = len(jax.live_arrays())
    # steady state: swap N holds exactly as many live buffers as swap 1
    assert len(jax.live_arrays()) == live_after_first
    assert dict(engine.compile_counts) == compiles0
    assert engine.stats()["param_version"] == 4
    # the swapped engine still decodes correctly (same params content)
    base = InferenceEngine(cfg, params, **_ENGINE_KW)
    assert engine.generate([prompt], max_new_tokens=4) == \
        base.generate([prompt], max_new_tokens=4)


def test_weight_swap_invalidates_prefix_cache(tiny_rl):
    """A weight swap must flush the content-keyed prefix cache: its
    pages hold K/V computed under the OLD params, so a post-swap
    request sharing the prefix would otherwise attend over stale
    context and its logprobs would silently diverge from
    ``forward(new_params)`` — breaking the on-policy contract."""
    import jax

    from ray_tpu.inference import InferenceEngine
    from ray_tpu.models.gpt import forward, init_params
    cfg, params = tiny_rl
    # a bucket big enough for a multi-page prompt (same geometry as
    # _ENGINE_KW otherwise, so the decode executable is shared)
    engine = InferenceEngine(cfg, params,
                             **{**_ENGINE_KW, "buckets": (16, 64)})
    prompt = list(
        np.random.RandomState(71).randint(0, cfg.vocab_size, 37))
    engine.generate([prompt], max_new_tokens=2)   # registers 2 pages
    assert engine.stats()["prefix"]["registered_pages"] == 2
    new_params = init_params(cfg, jax.random.PRNGKey(9))
    engine.set_params(jax.tree.map(np.asarray, new_params), version=1)
    # the index is empty and the idle pages are back in the free pool
    st = engine.stats()
    assert st["prefix"]["registered_pages"] == 0
    assert st["prefix"]["idle_pages"] == 0
    # the same prompt re-prefills cold (no hit) and its trajectory is
    # exactly what the NEW params produce, teacher-forced
    (toks,), (lps,) = engine.generate([prompt], max_new_tokens=4,
                                      return_logprobs=True)
    assert engine.stats()["prefix"]["requests_hit"] == 0
    import jax.numpy as jnp
    full = prompt + toks[:-1]
    logits, _ = forward(new_params, jnp.array(full, jnp.int32)[None],
                        cfg)
    rows = np.asarray(logits[0, len(prompt) - 1:len(prompt) - 1
                             + len(toks)])
    ref_lp = jax.nn.log_softmax(rows, axis=-1)
    assert toks == list(rows.argmax(-1))
    np.testing.assert_allclose(
        lps, [float(ref_lp[i, t]) for i, t in enumerate(toks)],
        rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ the queue
def test_replay_queue_staleness_and_overflow():
    from ray_tpu.rl import ReplayQueue
    from ray_tpu.rl.rollout import TrajectoryBatch

    def mk(version):
        z = np.zeros((1, 2), np.int32)
        return TrajectoryBatch(tokens=z, targets=z,
                               rewards=np.zeros(1, np.float32),
                               logprobs=[[]], completions=[[]],
                               param_version=version)

    q = ReplayQueue(2, max_lag=1, overflow="drop")
    assert q.put(mk(1)) and q.put(mk(2))
    assert q.put(mk(3)) and len(q) == 2     # evicted the oldest (v1)
    assert q.drops_overflow == 1
    # hard staleness bound: at current version 4, v2 lags by 2 > 1
    got = q.pop(current_version=4)
    assert got is not None and got.param_version == 3
    assert q.drops_stale == 1
    assert q.pop(4) is None

    w = ReplayQueue(1, max_lag=0, overflow="wait")
    assert w.put(mk(1))
    assert not w.put(mk(2))                 # backpressure, no drop
    assert w.drops_overflow == 0 and len(w) == 1
    assert w.pop(2) is None                 # v1 at version 2: stale
    assert w.drops_stale == 1
    assert w.drain() == []
    with pytest.raises(ValueError):
        ReplayQueue(0)
    with pytest.raises(ValueError):
        ReplayQueue(1, overflow="sometimes")


def test_replay_queue_staleness_fuzz():
    """Random publish/put/pop interleavings: the learner NEVER sees a
    batch more than max_lag publications old, the queue never exceeds
    capacity, and every put is accounted for (trained + dropped +
    drained = puts)."""
    from ray_tpu.rl import ReplayQueue
    from ray_tpu.rl.rollout import TrajectoryBatch

    rng = np.random.RandomState(7)
    z = np.zeros((1, 2), np.int32)

    def mk(version):
        return TrajectoryBatch(tokens=z, targets=z,
                               rewards=np.zeros(1, np.float32),
                               logprobs=[[]], completions=[[]],
                               param_version=version)

    for max_lag in (0, 1, 3):
        q = ReplayQueue(3, max_lag=max_lag, overflow="drop")
        version, trained, rejected = 1, 0, 0
        for _ in range(500):
            op = rng.rand()
            if op < 0.4:
                ok = q.put(mk(version))
                rejected += 0 if ok else 1
            elif op < 0.7:
                batch = q.pop(version)
                if batch is not None:
                    assert batch.param_version >= version - max_lag
                    trained += 1
            else:
                version += 1
            assert len(q) <= 3
        leftover = len(q.drain())
        # every accepted put is accounted for: trained, dropped for
        # staleness, evicted on overflow, or drained at shutdown
        assert q.puts == (trained + q.drops_stale + q.drops_overflow
                          + leftover)
        assert rejected == 0                  # drop policy never rejects


# --------------------------------------------------------------- the loop
def test_rl_loop_reward_improves_end_to_end(tiny_rl):
    """The end-to-end proof: REINFORCE/RLOO through the real
    actor/learner split (inference-engine rollouts, policy-gradient
    learner, versioned weight publications, bounded queue) improves
    the programmatic reward monotonically across thirds of the run,
    under fixed seeds on host-sim."""
    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.rl import RLConfig, run_rl_loop
    cfg, _params = tiny_rl
    rlcfg = RLConfig(actors=2, batch=6, horizon=8, queue=4, max_lag=1,
                     overflow="drop", publish_every=1, baseline="rloo",
                     temperature=1.0)
    res = run_rl_loop(cfg, steps=8, rlcfg=rlcfg, seed=3, lr=5e-2,
                      engine_kwargs=dict(_ENGINE_KW))
    curve = np.array(res["reward_curve"])
    thirds = [t.mean() for t in np.array_split(curve, 3)]
    assert thirds[0] < thirds[1] < thirds[2], curve
    assert curve[-1] > curve[0]
    # staleness honored end to end: nothing trained beyond the bound
    assert all(h["param_version_lag"] <= rlcfg.max_lag
               for h in res["history"])
    assert res["telemetry"]["version_lag_max"] <= rlcfg.max_lag
    # weight publication was recompile-free across the whole run: the
    # first actor compiled each step once, the second compiled nothing
    # (shared executable cache), despite res["publishes"] >= 9 swaps
    assert res["publishes"] >= res["steps"] + 1
    for stats in res["engine_stats"]:
        assert stats["compiles"]["decode"] <= 1
        assert stats["compiles"]["prefill"] <= 1
        assert stats["param_version"] >= 1
    # clean shutdown: queue drained, no engine slot/page leaks (the
    # scheduler invariants), nothing silently lost
    assert res["leftover_batches"] == 0
    for eng in res["actors"]:
        assert not eng.scheduler.active and not eng.scheduler.waiting


@pytest.mark.slow
def test_rl_loop_staleness_drops_over_lag_batches(tiny_rl):
    """max_lag=0 with three actor replicas racing one learner: the
    later replicas' batches go stale mid-round and must be DROPPED,
    never trained — the queue's drop counters and the trained-batch
    lag records agree."""
    from ray_tpu.rl import RLConfig, run_rl_loop
    cfg, _params = tiny_rl
    rlcfg = RLConfig(actors=3, batch=2, horizon=4, queue=4, max_lag=0,
                     overflow="drop", publish_every=1, baseline="rloo",
                     temperature=1.0)
    res = run_rl_loop(cfg, steps=4, rlcfg=rlcfg, seed=11, lr=1e-3,
                      engine_kwargs=dict(_ENGINE_KW))
    assert res["drops_stale"] > 0
    assert all(h["param_version_lag"] == 0 for h in res["history"])
    assert res["telemetry"]["drops"]["stale"] == res["drops_stale"]
    # the step budget can cut the loop mid-round; drained leftovers are
    # accounted, bounded by one in-flight batch per actor — not leaked
    assert res["leftover_batches"] <= rlcfg.actors


@pytest.mark.slow
def test_rl_loop_wait_policy_backpressure(tiny_rl):
    """overflow="wait" end to end: a full queue rejects the put, the
    actor HOLDS the batch and re-enqueues it once the learner drains —
    nothing evicted, nothing silently discarded, every rollout either
    trained, dropped-for-staleness (counted) or handed back at
    shutdown."""
    from ray_tpu.rl import RLConfig, run_rl_loop
    cfg, _params = tiny_rl
    rlcfg = RLConfig(actors=2, batch=2, horizon=4, queue=1, max_lag=8,
                     overflow="wait", publish_every=1, baseline="rloo",
                     temperature=1.0)
    res = run_rl_loop(cfg, steps=3, rlcfg=rlcfg, seed=13, lr=1e-3,
                      engine_kwargs=dict(_ENGINE_KW))
    assert res["steps"] == 3
    assert res["drops_overflow"] == 0          # wait never evicts
    tel = res["telemetry"]
    # rejections are counted as backpressure, NOT as drops — the held
    # batches are trained eventually
    assert tel["backpressure_rejections"] > 0
    assert "overflow_wait" not in tel["drops"]
    # full accounting: every rollout is trained, stale-dropped, or
    # returned at shutdown — none vanished into the full queue
    assert tel["rollouts"] == (res["steps"] + res["drops_stale"]
                               + res["leftover_batches"])


@pytest.mark.slow   # r14 --durations: 7s of jit; the slow learner-
                    # group test exercises this class end to end
def test_gpt_policy_learner_protocol(tiny_rl):
    """The LearnerGroup-hosted learner class, driven directly (no
    actors): init_state/update move params and report the PG metric
    schema — protocol parity with PPOLearner."""
    import jax

    from ray_tpu.rl import GPTPolicyLearner, RLLearnerConfig
    from ray_tpu.rl.rollout import trajectories_to_batch
    cfg, _params = tiny_rl
    learner = GPTPolicyLearner(cfg, RLLearnerConfig(lr=1e-2, seed=0))
    params, opt_state = learner.init_state(jax.random.PRNGKey(0))
    arrays = trajectories_to_batch([[1, 2, 3]] * 4,
                                   [[4, 5], [6, 7], [8, 9], [4, 4]],
                                   seq_len=8)
    batch = {**arrays, "rewards": np.array([1, 0, 0, 2], np.float32)}
    p0 = jax.tree.map(np.asarray, params)
    params, opt_state, metrics = learner.update(params, opt_state,
                                                batch)
    for key in ("pg_loss", "reward_mean", "entropy", "total_loss",
                "logp_mean"):
        assert np.isfinite(metrics[key]), (key, metrics)
    moved = jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - b))),
        params, p0)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.slow   # learner-actor subprocesses each pay a jax import
def test_rl_loop_on_learner_group(tiny_rl, ray_start_regular):
    """The RLlib learner group as the RL loop's learner host
    (num_learners=2): trajectory batches shard across learner actors,
    gradients ring-allreduce, weight snapshots publish through the
    object store, and the loop still improves the reward."""
    from ray_tpu.rl import RLConfig, run_rl_loop
    cfg, _params = tiny_rl
    rlcfg = RLConfig(actors=1, batch=6, horizon=8, queue=4, max_lag=1,
                     overflow="drop", publish_every=1, baseline="rloo",
                     temperature=1.0)
    res = run_rl_loop(cfg, steps=4, rlcfg=rlcfg, seed=3, lr=5e-2,
                      num_learners=2, engine_kwargs=dict(_ENGINE_KW))
    assert res["steps"] == 4
    assert res["param_version"] >= 5          # seed + one per step
    curve = res["reward_curve"]
    assert np.isfinite(curve).all()
    assert curve[-1] > curve[0]
    assert res["leftover_batches"] == 0
